"""The Nemotron-H cell's check held to its purpose, at the tiny preset of
`tests/test_nemotron_h.py` on the CPU: the comparison is the benchmark's own
(`ShareStepCheck` of `benchmark/drivers/resident_lm_share.py` over
`benchmark/check_lm.py`); each of the ten departures the cell's check must
catch on the chip is patched into the program
(`benchmark/rehearse/departures_nemotron_h.py`) and the comparison must FAIL;
the program as it is must pass. A file of its own so that two xdist workers
share the model's cases.
"""

import pytest

from tests.test_nemotron_h import LEAVES, TINY, departures, lm, reference

# float32 against float32: the only differences are the order of sums
TIGHT = {"loss_rel": 1e-5, "routing_agreement_min": 1.0,
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
    assert abs(figures["bias_abs_max"] - 2e-3) < 1e-8       # two steps of ±1e-3
    assert len(figures["router_same_input"]) == 2           # every step, not the first alone


@pytest.mark.parametrize("departure", [None] + sorted(departures.DEPARTURES))
def test_the_check_fails_on(departure, monkeypatch):
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check(departure)
    assert verdict["ok"] == (departure is None), (verdict["failures"], verdict["figures"])


@pytest.mark.parametrize("control", sorted(departures.CONTROLS)
                         + sorted(departures.BELOW_THE_NOISE))
def test_a_precision_control_shows_in_the_figures(control, monkeypatch):
    """The state-space path kept in bfloat16 where it is stated float32:
    here every matmul is float32, so the control alone makes the noise, and
    the float32-against-float32 limits must catch it in the first moments of
    the Mamba leaves (on the chip it is read against the bfloat16 matmuls'
    own noise: PERF.md §6)."""
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check(control)
    assert not verdict["ok"]
    assert any(f.startswith("mu_rel_l2.mamba_") for f in verdict["failures"]), verdict["failures"]


def test_experts_under_the_floor_of_pairs_are_pooled(monkeypatch):
    """With the floor above what any expert got, every slice is pooled into
    one judged unit; the verdict still holds."""
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 10 ** 6)
    verdict = lm.run_check()
    assert verdict["ok"], verdict["failures"]
    assert verdict["figures"]["experts_pooled"] == TINY["n_routed_experts"]
    assert "mu_rel_l2.w_up.worst_judged" in verdict["figures"]
