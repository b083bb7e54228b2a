"""The Qwen3-Next cell's check held to its purpose, at the tiny preset of
`tests/test_qwen3_next.py` on the CPU: the comparison is the benchmark's own
(`StatelessStepCheck` of `benchmark/drivers/resident_lm_stateless.py` over
`benchmark/check_lm.py`); each departure the cell's check must catch on the
chip, and each part the configuration states float32 kept in bfloat16 ALONE, is
patched into the program (`benchmark/rehearse/departures_qwen3_next.py`) and
the comparison must FAIL; the program as it is must pass. A file of its own so
that two xdist workers share the model's cases.
"""

import jax
import pytest

from tests.test_qwen3_next import LEAVES, TINY, departures, lm, reference

# float32 against float32: the only differences are the order of sums — and,
# for the auxiliary term, float32's resolution at the loss it is the difference
# of (`StatelessStepCheck`: total − cross entropy, 0.003 of 5.5)
TIGHT = {"loss_rel": 1e-5, "loss_ce_rel": 1e-5, "loss_aux_rel": 5e-4,
         "routing_agreement_min": 1.0,
         "router_same_input_agreement_min": 1.0, "router_weight_rel_median": 1e-6,
         "mu_rel_l2": {"default": 1e-4, "experts": 1e-4},
         "update_rel_l2": {"default": 2e-3, "experts": 2e-3}}


def tight(monkeypatch):
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)


@pytest.fixture(scope="module")
def as_it_is():
    """The verdict under the float32-against-float32 limits of the program as
    it is, run once."""
    with pytest.MonkeyPatch.context() as monkeypatch:
        tight(monkeypatch)
        return lm.run_check()


def test_two_adamw_steps_match_reference(as_it_is):
    assert as_it_is["ok"], as_it_is["failures"]
    figures = as_it_is["figures"]
    assert figures["leaves_compared"] == len(LEAVES)
    assert figures["experts_compared"] == TINY["num_experts"]
    assert len(figures["router_same_input"]) == 2           # every step, not the first alone
    for term in ("loss_ce", "loss_aux"):
        assert len(figures[f"{term}_program"]) == len(figures[f"{term}_reference"]) == 2
    assert figures["loss_ce_rel"] < 1e-5
    assert {f"mu_rel_l2.{leaf}" for leaf in LEAVES if leaf not in (
        "w_gate", "w_up", "w_down")} <= set(figures)


def test_the_check_passes_the_program_as_it_is(as_it_is):
    """Under the limits the chip's check runs with: each is looser than the
    float32-against-float32 one the program as it is has just passed."""
    assert as_it_is["ok"]
    chip = reference.TOLERANCES
    for name, limit in TIGHT.items():
        if isinstance(limit, dict):
            assert all(chip[name].get(leaf, chip[name]["default"]) >= limit["default"]
                       for leaf in set(chip[name]) | set(limit))
        elif name.endswith("_min"):
            assert chip[name] <= limit
        elif name != "loss_aux_rel":
            assert chip[name] >= limit


def terms_under(patch):
    """The loss terms of the first batch from the check's lively parameters,
    by the program with `patch` applied, traced inside the patch."""
    batch, params = lm.batches(steps=1)[0], lm.params(**lm.short)
    spec, _ = lm.fresh_trainer(**lm.short)
    with departures.applied(patch, lm.zoo), jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, b: lm.terms(spec, p, b))(params, batch)


def moved(patch):
    """The largest relative move of a loss term under `patch`."""
    want = lm.program_terms(**lm.short)(lm.params(**lm.short), lm.batches(steps=1)[0])
    got = terms_under(patch)
    return max(abs(float(got[t]) - float(want[t])) / abs(float(want[t])) for t in want)


# the check itself, end to end, on the departure that moves the loss most and
# on the one that moves it least
@pytest.mark.parametrize("departure", ["value_heads_on_the_wrong_key_head",
                                       "topk_weights_not_renormalised"])
def test_the_check_fails_on(departure, monkeypatch):
    """Under the limits the chip's check runs with."""
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check(departure)
    assert not verdict["ok"], verdict["figures"]
    assert any(f.startswith("loss") for f in verdict["failures"]), verdict["failures"]


@pytest.mark.parametrize("departure", sorted(departures.DEPARTURES))
def test_a_departure_moves_the_loss_ten_times_past_the_chip_s_limit(departure, as_it_is):
    """The reference gives the UNPATCHED program's loss to 1e-5 here (the
    check as it is, above), and the chip's check refuses a loss 7.5e-5 off: a
    departure that moves the program's own loss by ten times that turns the
    check red whatever else it reads."""
    assert as_it_is["figures"]["loss_ce_rel"] < 1e-5
    assert moved(departure) > 10 * reference.TOLERANCES["loss_ce_rel"]


@pytest.mark.parametrize("control", sorted({**departures.CONTROLS, **departures.BELOW_THE_NOISE}))
def test_a_precision_control_moves_the_float32_program_s_loss(control):
    """One float32 statement kept in bfloat16: here every matmul is float32,
    so the control alone makes the noise — the loss terms of the first batch
    move (the same program twice gives the same bits), and by far less than a
    departure of the model would move them (the chip's figures are
    `benchmark/rehearse/departures_qwen3_next.py`'s to read)."""
    assert 2e-7 < moved(control) < 2e-2, control


def test_every_departure_the_issue_names_has_a_patch():
    """Seven float32 statements, each broken alone: two the chip's check
    catches, five it reads like the program as it is (listed with their
    figures); every one moves the float32 program here (above)."""
    assert set(departures.CONTROLS) == {"decay_in_bfloat16", "a_bfloat16_router"}
    assert set(departures.BELOW_THE_NOISE) == {
        "state_in_bfloat16", "l2_norms_in_bfloat16", "residual_stream_in_bfloat16",
        "head_norms_in_bfloat16", "gated_norm_in_bfloat16"}
    assert set(departures.DEPARTURES) == {
        "gate_before_the_norm", "whole_head_rotated", "topk_weights_not_renormalised",
        "shared_expert_not_gated", "value_heads_on_the_wrong_key_head"}
    chip = reference.TOLERANCES
    assert all(0 < limit < 0.2 for leaf, limit in chip["mu_rel_l2"].items())
    assert chip["update_rel_l2"]["gdn_A_log"] == chip["update_rel_l2"]["gdn_dt_bias"] == 1.5
