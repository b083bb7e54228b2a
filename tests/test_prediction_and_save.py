"""Regression tests for round-4 verdict warts (VERDICT.md "What's weak"
3-5): SAVE_MODEL must not report success when there is nowhere to save,
and prediction outputs of ANY pytree shape must survive masking in both
worker flavors.
"""

import os
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.parallel.elastic import CohortContext
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.worker.cohort import OP_TASK, CohortWorker
from elasticdl_tpu.worker.prediction_outputs_processor import (
    iter_stacked,
    mask_predictions,
)
from elasticdl_tpu.worker.worker import Worker


def make_cfg(tmp_path, **overrides):
    base = dict(
        job_name="regress",
        model_zoo=os.path.abspath("model_zoo"),
        model_def="deepfm.deepfm.custom_model",
        training_data="synthetic://criteo?n=256&shards=1",
        minibatch_size=32,
        master_addr="localhost:1",
    )
    base.update(overrides)
    return JobConfig(**base)


# --------------------------------------------------------------------- #
# mask_predictions / iter_stacked: pytree-shaped prediction outputs


def test_mask_predictions_plain_array():
    valid = np.array([True, False, True, True])
    out = mask_predictions(np.arange(8.0).reshape(4, 2), valid)
    assert isinstance(out, np.ndarray) and out.shape == (3, 2)
    np.testing.assert_array_equal(out[0], [0.0, 1.0])


def test_mask_predictions_dict_and_tuple_pytree():
    valid = np.array([False, True, True])
    out = mask_predictions(
        {"logits": jnp.ones((3, 5)), "aux": (jnp.zeros((3,)), jnp.ones((3, 2)))},
        valid,
    )
    assert out["logits"].shape == (2, 5)
    assert out["aux"][0].shape == (2,)
    assert out["aux"][1].shape == (2, 2)


def test_iter_stacked_pytree_round_trip():
    stacked = {"a": jnp.arange(6.0).reshape(3, 2), "b": jnp.arange(3.0)}
    parts = list(iter_stacked(stacked, 3))
    assert len(parts) == 3
    np.testing.assert_array_equal(parts[1]["a"], [2.0, 3.0])
    assert float(parts[2]["b"]) == 2.0


def test_cohort_process_predictions_pytree(tmp_path):
    """cohort._process_predictions used to np.asarray() the allgathered
    outputs, crashing on dict/tuple predict outputs (VERDICT r4 weak #4).
    Single-process path: device_get + mask, leader consumes."""
    captured = []

    class Proc:
        def process(self, predictions, worker_id):
            captured.append(predictions)

    w = CohortWorker(make_cfg(tmp_path), ctx=CohortContext("localhost:1", 1, 0))
    w._spec = SimpleNamespace(prediction_outputs_processor=Proc())
    host_batch = {"mask": np.array([1, 1, 0, 1])}
    outputs = {"score": jnp.arange(4.0), "emb": jnp.ones((4, 3))}
    w._process_predictions(outputs, host_batch)
    assert len(captured) == 1
    np.testing.assert_array_equal(captured[0]["score"], [0.0, 1.0, 3.0])
    assert captured[0]["emb"].shape == (3, 3)


# --------------------------------------------------------------------- #
# SAVE_MODEL with no checkpoint_dir must fail the task, not lie


def test_cohort_save_model_without_checkpoint_dir_fails_task(tmp_path):
    """VERDICT r4 weak #3: a SAVE_MODEL task on a cohort configured
    without checkpoint_dir reported success while saving nothing. It must
    report failure so the dispatcher's bounded retries surface it."""
    reports = []

    class Stub:
        def ReportTaskResult(self, req, timeout=None):
            reports.append(req)

    w = CohortWorker(make_cfg(tmp_path), ctx=CohortContext("localhost:1", 1, 0))
    w._session.stub = Stub()
    assert not w.cfg.checkpoint_dir
    w._run_task([OP_TASK, 7, pb.SAVE_MODEL, 0, 0, 0, 0, 0, 0])
    assert len(reports) == 1
    assert reports[0].success is False
    assert "checkpoint_dir" in reports[0].err_message


def test_worker_save_model_without_checkpoint_dir_raises():
    """Plain-worker twin: _save_checkpoint silently returned on a missing
    checkpoint manager; the task loop then reported success. It must
    raise, which the loop converts into a failed task report."""
    fake = SimpleNamespace(_checkpoint_manager=lambda: None)
    with pytest.raises(RuntimeError, match="checkpoint_dir"):
        Worker._save_checkpoint(fake)
