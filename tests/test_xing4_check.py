"""The Xing4.0 cell's check held to its purpose, at the tiny preset of
`tests/test_xing4.py` on the CPU: the comparison is the benchmark's own
(`ModelStepCheck` of `benchmark/drivers/resident_lm_model.py` over
`benchmark/check_lm.py`); each departure the cell's check must catch on the
chip is patched into the program (`benchmark/rehearse/departures_xing4.py`)
and the comparison must FAIL; the program as it is must pass. A file of its
own so that two xdist workers share the model's cases.
"""

import pytest

from benchmark import common
from tests.test_xing4 import LEAVES, TINY, departures, driver, lm, reference

# float32 against float32: the only differences are the order of sums
TIGHT = {"loss_rel": 1e-5, "loss_ce_rel": 1e-5,
         # a row sum less one: float32's last bits are a thousandth of it
         "mhc_sinkhorn_residual_rel": 2e-2,
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
    assert figures["experts_compared"] == TINY["n_routed_experts"]
    assert figures["bias_entries_off_share"] == 0.0
    assert 0 < figures["bias_abs_max"] <= 2 * 1e-3 + 1e-9   # two steps of ±1e-3
    assert len(figures["router_same_input"]) == 2           # every step, not the first alone
    assert len(figures["loss_ce_program"]) == len(figures["loss_ce_reference"]) == 2
    assert figures["loss_ce_rel"] < 1e-5
    assert figures["mhc_sinkhorn_residual_rel"] < 2e-2
    assert {f"mu_rel_l2.{leaf}" for leaf in ("hc_phi", "hc_alpha", "hc_b")} <= set(figures)


@pytest.mark.parametrize("departure", [None] + sorted(departures.DEPARTURES))
def test_the_check_fails_on(departure, monkeypatch):
    """Float32 against float32, a departure is all the difference there is."""
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check(departure)
    assert verdict["ok"] == (departure is None), (verdict["failures"], verdict["figures"])


@pytest.mark.parametrize("control", sorted({**departures.CONTROLS,
                                             **departures.BELOW_THE_NOISE}))
def test_a_precision_control_shows_in_the_figures(control, monkeypatch):
    """A part stated float32 kept in bfloat16 (the Sinkhorn rounds; the
    coefficients before them; the router's scores): here every matmul is
    float32, so the control alone makes the noise, and the
    float32-against-float32 limits must catch it — those the chip's check
    cannot see (`BELOW_THE_NOISE`) among them."""
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check(control)
    assert not verdict["ok"]
    assert any(f.startswith(("mu_rel_l2.", "update_rel_l2.", "router_"))
               for f in verdict["failures"]), verdict["failures"]


@pytest.mark.parametrize("case, at_least", [
    ("ten_sinkhorn_rounds", 0.5), ("sinkhorn_in_bfloat16", 0.5), ("h_res_the_identity", 0.99)])
def test_the_reported_residual_tells_the_rounds(case, at_least, monkeypatch):
    """`mhc_sinkhorn_residual` against the reference's: what holds the
    rounds' number and their precision on the chip, where the parameters'
    updates are too noisy to (PERF.md §6, PR 48)."""
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check(case)
    assert verdict["figures"]["mhc_sinkhorn_residual_rel"] > at_least
    assert any(f.startswith("mhc_sinkhorn_residual_rel") for f in verdict["failures"])


def test_every_departure_the_issue_names_has_a_patch():
    assert set(departures.DEPARTURES) == {
        "ten_sinkhorn_rounds", "h_res_the_identity", "h_post_without_its_factor_2",
        "yarn_softmax_factor_left_out", "v_at_the_wrong_128", "renormalisation_left_out",
        "scaling_factor_left_out"}
    assert set(departures.CONTROLS) == {"sinkhorn_in_bfloat16", "a_bfloat16_router"}
    assert set(departures.BELOW_THE_NOISE) == {"coefficients_in_bfloat16"}
    assert 0 < reference.TOLERANCES["bias_entries_off_share"] < 0.5


def test_a_departure_s_trainer_does_not_get_another_s_compiled_step():
    """The departures' trainers take a program token of their own
    (`fresh_trainer`), else the second would be handed the first one's
    compiled, unpatched step."""
    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    config = {"model_def": "transformer.xing4.custom_model",
              "model_params": common.format_model_params(lm.tiny_params())}
    data = lm.batches(steps=1)[0]
    losses = {}
    for name in (None, "h_post_without_its_factor_2"):
        spec, mesh, trainer, module = departures._glm.fresh_trainer(driver, config, 3)
        with departures.applied(name, module):
            state = lm.lively(trainer.init_state(data))
            _, m = trainer.train_many(state, shard_batch_stack(
                mesh, [data], spec.batch_partition))
        losses[name] = float(m["loss_ce"][0])
    assert abs(losses[None] - losses["h_post_without_its_factor_2"]) > 1e-5
