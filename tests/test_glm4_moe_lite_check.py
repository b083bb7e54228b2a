"""The GLM-4.7-Flash cell's check held to its purpose, at the tiny preset of
`tests/test_glm4_moe_lite.py` on the CPU: the comparison is the benchmark's
own (`ModelStepCheck` of `benchmark/drivers/resident_lm_model.py` over
`benchmark/check_lm.py`); each departure the cell's check must catch on the
chip is patched into the program
(`benchmark/rehearse/departures_glm4_moe_lite.py`) and the comparison must
FAIL; the program as it is must pass. The float32-against-float32 limits are
in `tests/test_glm4_moe_lite_tight.py`: three files, so that three xdist
workers share the model's cases.
"""

import pytest

from benchmark import common
from tests.test_glm4_moe_lite import departures, driver, lm, reference


@pytest.mark.parametrize("departure", [None] + sorted(departures.DEPARTURES))
def test_the_check_fails_on(departure, monkeypatch):
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = lm.run_check(departure)
    assert verdict["ok"] == (departure is None), (verdict["failures"], verdict["figures"])


def test_a_departure_s_trainer_does_not_get_another_s_compiled_step():
    """`drivers/resident.py::build_trainer` gives its trainers the job's
    program token, under which a second trainer of the same configuration is
    handed the first one's compiled step: a patched program would run
    unpatched. The departures' trainers take a token of their own."""
    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    config = {"model_def": "transformer.glm4_moe_lite.custom_model",
              "model_params": common.format_model_params(lm.tiny_params())}
    data = lm.batches(steps=1)[0]
    losses = {}
    for name in (None, "mtp_weight_zero"):
        spec, mesh, trainer, module = departures.fresh_trainer(driver, config, 3)
        with departures.applied(name, module):
            state = trainer.init_state(data)
            _, m = trainer.train_many(state, shard_batch_stack(
                mesh, [data], spec.batch_partition))
        losses[name] = {k: float(v[0]) for k, v in m.items()}
    assert abs(losses[None]["loss"] - losses[None]["loss_main"]
               - 0.3 * losses[None]["loss_mtp"]) < 1e-5
    assert abs(losses["mtp_weight_zero"]["loss"]
               - losses["mtp_weight_zero"]["loss_main"]) < 1e-6
