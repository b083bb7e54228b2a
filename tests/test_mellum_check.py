"""The Mellum2 cell's check held to its purpose, at the tiny preset of
`tests/test_mellum.py` on the CPU: the comparison is the benchmark's own
(`StatelessStepCheck` of `benchmark/drivers/resident_lm_stateless.py` over
`benchmark/check_lm.py`); each departure the cell's check must catch on the
chip is patched into the program (`benchmark/rehearse/departures_mellum.py`)
and the comparison must FAIL; the program as it is must pass. A file of its
own so that two xdist workers share the model's cases.
"""

import pytest

from benchmark import common
from tests.test_mellum import LEAVES, TINY, departures, driver, lm, reference

# float32 against float32: the only differences are the order of sums
TIGHT = {"loss_rel": 1e-5, "loss_ce_rel": 1e-5, "loss_aux_rel": 2e-4,
         "routing_agreement_min": 1.0,
         "router_same_input_agreement_min": 1.0, "router_weight_rel_median": 1e-5,
         "mu_rel_l2": {"default": 1e-4, "experts": 1e-4},
         "update_rel_l2": {"default": 2e-3, "experts": 2e-3}}


def test_two_adamw_steps_match_reference(monkeypatch):
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check()
    assert verdict["ok"], verdict["failures"]
    figures = verdict["figures"]
    assert figures["leaves_compared"] == len(LEAVES)
    assert figures["experts_compared"] == TINY["num_experts"]
    assert len(figures["router_same_input"]) == 2           # every step
    assert len(figures["loss_ce_program"]) == len(figures["loss_aux_reference"]) == 2
    assert figures["loss_ce_rel"] < 1e-5 and figures["loss_aux_rel"] < 2e-4


@pytest.mark.parametrize("departure", [None] + sorted(departures.DEPARTURES))
def test_the_check_fails_on(departure, monkeypatch):
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check(departure)
    assert verdict["ok"] == (departure is None), (verdict["failures"], verdict["figures"])


@pytest.mark.parametrize("control", sorted(departures.CONTROLS))
def test_a_precision_control_shows_in_the_figures(control, monkeypatch):
    """A part stated float32 kept in bfloat16 (the router's logits; the
    residual stream): here every matmul is float32, so the control alone
    makes the noise, and the float32-against-float32 limits must catch it."""
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check(control)
    assert not verdict["ok"]
    assert any(f.startswith(("mu_rel_l2.", "router_")) for f in verdict["failures"]), \
        verdict["failures"]


def test_a_departure_s_trainer_does_not_get_another_s_compiled_step():
    """The departures' trainers take a program token of their own
    (`fresh_trainer`), else the second would be handed the first one's
    compiled, unpatched step."""
    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    config = {"model_def": "transformer.mellum.custom_model",
              "model_params": common.format_model_params(lm.tiny_params())}
    data = lm.batches(steps=1)[0]
    losses = {}
    for name in (None, "topk_weights_not_renormalised"):
        spec, mesh, trainer, module = departures.fresh_trainer(driver, config, 3)
        with departures.applied(name, module):
            state = lm.lively(trainer.init_state(data))
            _, m = trainer.train_many(state, shard_batch_stack(
                mesh, [data], spec.batch_partition))
        losses[name] = float(m["loss_ce"][0])
    assert abs(losses[None] - losses["topk_weights_not_renormalised"]) > 1e-5
