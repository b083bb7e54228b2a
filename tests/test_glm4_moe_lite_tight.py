"""The GLM-4.7-Flash cell's check under the float32-against-float32 limits, at
the tiny preset of `tests/test_glm4_moe_lite.py` on the CPU, where every
matmul is float32: two AdamW steps of the program as it is agree with the
reference to the order of sums, and each precision control
(`benchmark/rehearse/departures_glm4_moe_lite.py`: a part stated float32 kept
in bfloat16) alone makes noise those limits catch. The departures under the
chip's own limits are in `tests/test_glm4_moe_lite_check.py`.
"""

import pytest

from tests.test_glm4_moe_lite import LEAVES, TINY, departures, lm, reference

# float32 against float32: the only differences are the order of sums
TIGHT = {"loss_rel": 1e-5, "loss_main_rel": 1e-5, "loss_mtp_rel": 1e-5,
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
    assert abs(figures["bias_abs_max"] - 2e-3) < 1e-8       # two steps of ±1e-3
    assert len(figures["router_same_input"]) == 2           # every step, not the first alone
    # the two terms apart, at both steps
    assert len(figures["loss_main_program"]) == len(figures["loss_mtp_reference"]) == 2
    assert figures["loss_main_rel"] < 1e-5 and figures["loss_mtp_rel"] < 1e-5


@pytest.mark.parametrize("control", sorted(departures.CONTROLS)
                         + sorted(departures.BELOW_THE_NOISE))
def test_a_precision_control_shows_in_the_figures(control, monkeypatch):
    """A part stated float32 kept in bfloat16 (the router's scores; what a
    sub-block adds to the residual stream; the latents before their norms):
    here every matmul is float32, so the control alone makes the noise, and
    the float32-against-float32 limits must catch it (on the chip it is read
    against the bfloat16 matmuls' own noise, and the last of the three drowns
    in it: PERF.md §6)."""
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check(control)
    assert not verdict["ok"]
    assert any(f.startswith(("mu_rel_l2.", "router_")) for f in verdict["failures"]), \
        verdict["failures"]
