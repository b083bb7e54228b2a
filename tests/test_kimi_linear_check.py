"""The Kimi Linear cell's check held to its purpose, at the tiny preset of
`tests/test_kimi_linear.py` on the CPU: the comparison is the benchmark's own
(`ModelStepCheck` of `benchmark/drivers/resident_lm_model.py` over
`benchmark/check_lm.py`); each departure the cell's check must catch on the
chip is patched into the program (`benchmark/rehearse/departures_kimi_linear.py`)
and the comparison must FAIL; the program as it is must pass. A file of its
own so that two xdist workers share the model's cases.
"""

import pytest

from tests.test_kimi_linear import LEAVES, TINY, departures, lm, reference

# float32 against float32: the only differences are the order of sums
TIGHT = {"loss_rel": 1e-5, "loss_ce_rel": 1e-5,
         "routing_agreement_min": 1.0,
         "router_same_input_agreement_min": 1.0, "router_weight_rel_median": 1e-5,
         "mu_rel_l2": {"default": 2e-4, "experts": 2e-4},
         "update_rel_l2": {"default": 3e-3, "experts": 3e-3},
         "bias_entries_off_share": 0.0}


def tight(monkeypatch):
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)


def test_two_adamw_steps_with_the_bias_update_match_reference(monkeypatch):
    tight(monkeypatch)
    verdict = lm.run_check()
    assert verdict["ok"], verdict["failures"]
    figures = verdict["figures"]
    # two layers of the five, one of each kind: no leaf is without a layer
    assert figures["leaves_compared"] == len(LEAVES)
    assert figures["experts_compared"] == TINY["num_experts"]
    assert figures["bias_entries_off_share"] == 0.0
    assert 0 < figures["bias_abs_max"] <= 2 * 1e-3 + 1e-9   # two steps of ±1e-3
    assert len(figures["router_same_input"]) == 2           # every step, not the first alone
    assert len(figures["loss_ce_program"]) == len(figures["loss_ce_reference"]) == 2
    assert figures["loss_ce_rel"] < 1e-5
    assert {f"update_rel_l2.{leaf}" for leaf in (
        "kda_wq", "kda_wk", "kda_wv", "kda_conv_q", "kda_conv_k", "kda_conv_v", "kda_f_a",
        "kda_f_b", "kda_A_log", "kda_dt_bias", "kda_beta", "kda_g_a", "kda_g_b", "kda_onorm",
        "kda_wo", "q_proj", "kv_a", "kv_b")} <= set(figures)


# the three the issue names for tier-1, the two that guard the mixer first
@pytest.mark.parametrize("departure", [
    "scalar_decay_a_head", "erase_term_left_out", "rotary_in_the_latent_layer"])
def test_the_check_fails_on(departure, monkeypatch):
    """Float32 against float32, a departure is all the difference there is
    (the program as it is passes: the case above)."""
    tight(monkeypatch)
    verdict = lm.run_check(departure)
    assert not verdict["ok"], verdict["figures"]
    assert any(f.startswith(("mu_rel_l2.", "update_rel_l2.", "loss")) for f in
               verdict["failures"]), verdict["failures"]


def test_every_departure_the_issue_names_has_a_patch():
    assert set(departures.DEPARTURES) == {
        "scalar_decay_a_head", "erase_term_left_out", "beta_doubled", "k_l2_norm_left_out",
        "output_gate_left_out", "rotary_in_the_latent_layer", "k_r_left_out_of_the_scores",
        "glm_low_rank_query_put_in"}
    assert set(departures.CONTROLS) | set(departures.BELOW_THE_NOISE) == {
        "cumulative_decay_in_bfloat16", "state_in_bfloat16"}
    assert set(departures.MUST_FAIL) <= set(departures.DEPARTURES)
    assert not set(departures.MUST_FAIL) & set(departures.BELOW_THE_NOISE)
    assert 0 < reference.TOLERANCES["bias_entries_off_share"] < 0.5
