"""The Trinity-Mini cell's check held to its purpose, at the tiny preset of
`tests/test_afmoe.py` on the CPU: the comparison is the benchmark's own
(`ModelStepCheck` of `benchmark/drivers/resident_lm_model.py` over
`benchmark/check_lm.py`); each departure the cell's check must catch on the
chip is patched into the program (`benchmark/rehearse/departures_afmoe.py`)
and the comparison must FAIL; the program as it is must pass. A file of its
own so that two xdist workers share the model's cases.
"""

import pytest

from benchmark import common
from tests.test_afmoe import LEAVES, TINY, departures, driver, lm, reference

# float32 against float32: the only differences are the order of sums
TIGHT = {"loss_rel": 1e-5, "loss_ce_rel": 1e-5,
         "routing_agreement_min": 1.0,
         "router_same_input_agreement_min": 1.0, "router_weight_rel_median": 1e-5,
         "mu_rel_l2": {"default": 1e-4, "experts": 1e-4},
         "update_rel_l2": {"default": 2e-3, "experts": 2e-3},
         "bias_entries_off_share": 0.0}


def test_two_adamw_steps_with_the_bias_update_match_reference(monkeypatch):
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check()
    assert verdict["ok"], verdict["failures"]
    figures = verdict["figures"]
    assert figures["leaves_compared"] == len(LEAVES)
    assert figures["experts_compared"] == TINY["num_experts"]
    assert figures["bias_entries_off_share"] == 0.0
    assert 0 < figures["bias_abs_max"] <= 2 * 1e-3 + 1e-9   # two steps of ±1e-3, summed
    assert len(figures["router_same_input"]) == 2           # every step, not the first alone
    assert len(figures["loss_ce_program"]) == len(figures["loss_ce_reference"]) == 2
    assert figures["loss_ce_rel"] < 1e-5


@pytest.mark.parametrize("departure", [None] + sorted(departures.DEPARTURES))
def test_the_check_fails_on(departure, monkeypatch):
    """Under the limits the chip's check runs with."""
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check(departure)
    assert verdict["ok"] == (departure is None), (verdict["failures"], verdict["figures"])


@pytest.mark.parametrize("control", sorted(departures.CONTROLS))
def test_a_precision_control_shows_in_the_figures(control, monkeypatch):
    """A part stated float32 kept in bfloat16 (the router's scores; the
    residual stream; the attention block's activations): here every matmul is
    float32, so the control alone makes the noise, and the
    float32-against-float32 limits must catch it (on the chip all but the
    first drown in the bfloat16 matmuls' own noise:
    `BELOW_THE_NOISE_ON_THE_CHIP`)."""
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check(control)
    assert not verdict["ok"]
    assert any(f.startswith(("mu_rel_l2.", "router_")) for f in verdict["failures"]), \
        verdict["failures"]


def test_every_departure_the_issue_names_has_a_patch():
    assert set(departures.DEPARTURES) == {
        "gate_left_out", "gate_after_the_output_projection", "gate_from_the_unnormed_input",
        "rotation_in_the_full_layers", "no_rotation_in_the_sliding_layers",
        "qk_norm_left_out", "qk_norm_after_the_rotation", "post_attn_norm_left_out",
        "post_mlp_norm_left_out",
        "embedding_multiplier_left_out", "route_scale_left_out", "weights_not_renormalised",
        "bias_used_as_a_weight", "window_one_key_short", "window_one_key_long",
        "shared_expert_left_out",
        # not the issue's: the one piece of router state the configuration adds
        "bias_update_left_out", "bias_update_mis_signed"}
    assert set(departures.CONTROLS) == {"a_bfloat16_router", "residual_stream_in_bfloat16",
                                        "attention_activations_in_bfloat16"}
    assert departures.BELOW_THE_NOISE_ON_THE_CHIP <= set(departures.ALL)


def test_a_departure_s_trainer_does_not_get_another_s_compiled_step():
    """The departures' trainers take a program token of their own
    (`fresh_trainer`), else the second would be handed the first one's
    compiled, unpatched step."""
    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    config = {"model_def": "transformer.afmoe.custom_model",
              "model_params": common.format_model_params(lm.tiny_params())}
    data = lm.batches(steps=1)[0]
    losses = {}
    for name in (None, "gate_left_out"):
        spec, mesh, trainer, module = departures.fresh_trainer(driver, config, 3)
        with departures.applied(name, module):
            state = lm.lively(trainer.init_state(data))
            _, m = trainer.train_many(state, shard_batch_stack(
                mesh, [data], spec.batch_partition))
        losses[name] = float(m["loss_ce"][0])
    assert abs(losses[None] - losses["gate_left_out"]) > 1e-5
