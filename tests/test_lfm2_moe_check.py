"""The LFM2-8B-A1B cell's check held to its purpose, at the tiny preset of
`tests/test_lfm2_moe.py` on the CPU: the comparison is the benchmark's own
(`ModelStepCheck` of `benchmark/drivers/resident_lm_model.py` over
`benchmark/check_lm.py`); each departure the cell's check must catch on the
chip is patched into the program (`benchmark/rehearse/departures_lfm2_moe.py`)
and the comparison must FAIL; the program as it is must pass. A file of its
own so that two xdist workers share the model's cases.
"""

import pytest

from tests.test_lfm2_moe import LEAVES, TINY, departures, driver, lm, reference

# float32 against float32: the only differences are the order of sums
TIGHT = {"loss_rel": 1e-5, "loss_ce_rel": 1e-5,
         "routing_agreement_min": 1.0,
         "router_same_input_agreement_min": 1.0, "router_weight_rel_median": 1e-6,
         "mu_rel_l2": {"default": 1e-4, "experts": 1e-4},
         "update_rel_l2": {"default": 2e-3, "experts": 2e-3},
         "bias_entries_off_share": 0.0}


def tight(monkeypatch):
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)


@pytest.fixture(scope="module")
def as_it_is():
    """(verdict under the float32-against-float32 limits, the checker that
    gave it) of the program as it is, run once: what a reference control
    stands in for."""
    with pytest.MonkeyPatch.context() as monkeypatch:
        tight(monkeypatch)
        with departures.keeping_the_checker(driver) as kept:
            verdict = lm.run_check()
    return verdict, kept[0]


def test_two_adamw_steps_with_the_bias_update_match_reference(as_it_is):
    verdict, _ = as_it_is
    assert verdict["ok"], verdict["failures"]
    figures = verdict["figures"]
    assert figures["leaves_compared"] == len(LEAVES)
    assert figures["experts_compared"] == TINY["num_experts"]
    assert figures["bias_entries_off_share"] == 0.0
    assert 0 < figures["bias_abs_max"] <= 2 * 1e-3 + 1e-9   # two steps of ±1e-3
    assert len(figures["router_same_input"]) == 2           # every step, not the first alone
    assert len(figures["loss_ce_program"]) == len(figures["loss_ce_reference"]) == 2
    assert figures["loss_ce_rel"] < 1e-5
    assert {f"update_rel_l2.{leaf}" for leaf in (
        "conv_in", "conv_w", "conv_out", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
        "embed", "moe_router")} <= set(figures)


def test_the_check_passes_the_program_as_it_is(as_it_is):
    """Under the limits the chip's check runs with: each is looser than the
    float32-against-float32 one the program as it is has just passed."""
    assert as_it_is[0]["ok"]
    chip = reference.TOLERANCES
    for name, limit in TIGHT.items():
        if isinstance(limit, dict):
            assert all(chip[name].get(leaf, chip[name]["default"]) >= limit["default"]
                       for leaf in set(chip[name]) | set(limit))
        elif name.endswith("_min"):
            assert chip[name] <= limit
        else:
            assert chip[name] >= limit


@pytest.mark.parametrize("departure", sorted(departures.DEPARTURES))
def test_the_check_fails_on(departure, monkeypatch):
    """Under the limits the chip's check runs with."""
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check(departure)
    assert not verdict["ok"], verdict["figures"]


@pytest.mark.parametrize("control", sorted(departures.CONTROLS))
def test_a_precision_control_shows_in_the_figures(control, monkeypatch):
    """The mixer's float32 planes kept in bfloat16: here every matmul is
    float32, so the control alone makes the noise, and the
    float32-against-float32 limits must catch it."""
    tight(monkeypatch)
    verdict = lm.run_check(control)
    assert not verdict["ok"]
    assert any(f.startswith(("mu_rel_l2.", "router_", "routing_")) for f in
               verdict["failures"]), verdict["failures"]


def test_the_reference_in_bfloat16_in_the_program_s_place_reads_false(as_it_is, monkeypatch):
    """The plain reference's own two steps computed in bfloat16, on the
    program's routing, held to the chip's limits as the program's are."""
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = departures.reference_in_the_program_s_place(
        "reference_in_bfloat16", driver, reference, lm.tiny_params(**lm.short), lm.batches(),
        as_it_is[1])
    assert not verdict["ok"], verdict["figures"]


def test_the_renormaliser_s_constant_is_reported_and_not_required_to_fail(monkeypatch):
    """1e-20 for 1e-6 moves a weight by a millionth of itself: under the
    float32-against-float32 limits the check reads it (the weights' median
    error on the same input), under the chip's it cannot."""
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    assert set(departures.REPORTED) == {"renormaliser_1e-20"}
    figures = lm.run_check("renormaliser_1e-20")["figures"]
    assert 1e-8 < figures["router_weight_rel_median"] < 5e-6


def test_every_departure_the_issue_names_has_a_patch():
    assert set(departures.DEPARTURES) == {
        "gate_g_left_out", "blocks_permuted", "tap_dropped", "head_norms_left_out",
        "bias_used_as_a_weight", "one_held_expert_left_out"}
    assert set(departures.CONTROLS) == {"conv_planes_in_bfloat16"}
    assert set(departures.BELOW_THE_NOISE_ON_THE_CHIP) <= set(departures.CONTROLS)
    assert set(departures.REFERENCE_CONTROLS) == {"reference_in_bfloat16"}
    assert not set(departures.REPORTED) & set(departures.DEPARTURES)
    assert 0 < reference.TOLERANCES["bias_entries_off_share"] < 0.5
