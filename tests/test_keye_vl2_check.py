"""The Keye-VL-2.0 cell's check held to its purpose, at the tiny preset of
`tests/test_keye_vl2.py` on the CPU: the comparison is the benchmark's own
(`DsaStepCheck` of `benchmark/drivers/resident_lm_dsa.py` over
`benchmark/check_lm.py`); each departure the check must catch on the chip is
patched into the program (`benchmark/rehearse/departures_keye_vl2.py`) and the
comparison must FAIL; the program as it is must pass.
"""

import pytest

from tests.test_keye_vl2 import lm, reference

departures = lm.departures
# float32 against float32: the only differences are the order of sums
TIGHT = {"loss_rel": 1e-5, "loss_ce_rel": 1e-5, "loss_balance_rel": 2e-4,
         "loss_index_rel": 2e-4, "routing_agreement_min": 1.0,
         "router_same_input_agreement_min": 1.0, "router_weight_rel_median": 1e-5,
         "index_score_rel": 1e-5, "selection_agreement_min": 1.0,
         "selection_agreement_mean_min": 1.0,
         "selection_outside_error_max": 0,
         "mu_rel_l2": {"default": 1e-4, "experts": 1e-4},
         "update_rel_l2": {"default": 2e-3, "experts": 2e-3}}

# bit for bit the same on the CPU, where a recomputed score is the forward's:
# its guard here is `test_thresholds_and_keep_are_kept_across_the_recomputation`,
# its reading on the chip PERF.md's
ONLY_ON_THE_CHIP = {"selection_redone_in_the_backward_pass"}


@pytest.mark.parametrize("departure", [None] + sorted(
    (set(departures.DEPARTURES) | set(departures.CONTROLS)) - ONLY_ON_THE_CHIP))
def test_the_check_fails_on(departure, monkeypatch):
    """Float32 against float32, so the float32 limits: every departure, and
    each part stated float32 kept in bfloat16, must fail one of them."""
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check(departure)
    assert verdict["ok"] == (departure is None), (verdict["failures"], verdict["figures"])


def test_every_departure_the_issue_names_has_a_patch():
    assert set(departures.DEPARTURES) >= {
        "one_key_short", "one_key_long", "future_keys_compete", "no_relu",
        "head_weights_left_out", "index_loss_left_out", "target_not_divided_by_heads",
        "target_not_detached", "indexer_input_not_detached",
        "selection_redone_in_the_backward_pass", "topk_weights_not_renormalised",
        "qk_norm_left_out"}
    assert set(departures.CONTROLS) == {"index_scores_in_bfloat16",
                                        "residual_stream_in_bfloat16"}
