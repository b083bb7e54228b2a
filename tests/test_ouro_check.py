"""The Ouro cell's check held to its purpose, at the tiny preset of
`tests/test_ouro.py` on the CPU: the comparison is the benchmark's own
(`DenseStepCheck` of `benchmark/drivers/resident_lm_dense.py`); each departure
the cell's check must catch on the chip is patched into the program
(`benchmark/rehearse/departures_ouro.py`) and the comparison must FAIL; the
program as it is must pass. A file of its own so that two xdist workers share
the model's cases.
"""

import pytest

from benchmark import check_lm
from tests.test_ouro import LEAVES, PASSES, departures, driver, lm, reference

# float32 against float32: the only differences are the order of sums
TIGHT = {"loss_rel": 1e-5, "loss_expected_rel": 1e-5, "loss_entropy_rel": 1e-4,
         **{f"loss_exit_{t + 1}_rel": 1e-5 for t in range(PASSES)},
         "exit_pmf_abs": 1e-5,
         "mu_rel_l2": {"default": 1e-4}, "update_rel_l2": {"default": 2e-3}}


def test_two_adamw_steps_match_reference(monkeypatch):
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    verdict = lm.run_check()
    assert verdict["ok"], verdict["failures"]
    figures = verdict["figures"]
    assert figures["leaves_compared"] == len(LEAVES)
    for term in ("loss", "loss_expected", "loss_entropy", f"loss_exit_{PASSES}"):
        assert len(figures[f"{term}_program"]) == len(figures[f"{term}_reference"]) == 2
    assert len(figures["exit_pmf_program"]) == 2 and len(figures["exit_pmf_program"][0]) == PASSES
    assert all(f"mu_rel_l2.{leaf}" in figures and f"update_rel_l2.{leaf}" in figures
               for leaf in LEAVES)


# two of the seven the cell's check must catch on the chip: a pass left out of
# the shared weights' gradient (no term of the loss moves: the moments alone
# hold it) and a precision control (here every matmul is float32, so the
# control alone makes the noise and the float32-against-float32 limits must
# catch it). `tests/test_ouro.py` holds every patch to what it changes.
SHOWS_IN = {
    "second_pass_left_out_of_the_shared_gradient": "mu_rel_l2.wq",
    "residual_stream_in_bfloat16": "mu_rel_l2.wq",
}


@pytest.mark.parametrize("departure", sorted(SHOWS_IN))
def test_the_check_fails_on(departure, monkeypatch):
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    verdict = lm.run_check(departure)
    assert not verdict["ok"]
    assert SHOWS_IN[departure] in {f.split(" ")[0] for f in verdict["failures"]}, \
        verdict["failures"]
    if departure.startswith("second_pass"):      # the forward pass is untouched
        assert verdict["figures"]["loss_rel"] < 1e-5


def test_the_check_fails_on_the_reference_in_the_precision_below(monkeypatch):
    """The contract's control: the reference's own two steps, computed in
    bfloat16 from float32 master weights, where the program's are read (the
    chip's script runs it with bfloat16 master weights too)."""
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    verdict = departures.reference_in_the_program_s_place(
        "reference_in_bfloat16_float32_optimizer", driver, reference, lm.tiny_params(),
        lm.batches(), check_lm._host(lm.params()), driver.DenseStepCheck.reference_steps)
    failed = {f.split(" ")[0] for f in verdict["failures"]}
    assert {"loss_rel", "mu_rel_l2.wq"} <= failed, verdict["failures"]
    # float32 master weights take the step: the update is off by the
    # gradients' precision, not by the weights' own rounding
    assert verdict["figures"]["update_rel_l2.embed"] < 1.0
