"""Evaluation-only and prediction-only jobs through the SPMD cohort
(worker/cohort.py) and, at one process, the plain worker: real subprocesses
driven by the in-process master, as in `tests/test_cohort.py`. A file of its
own so that two xdist workers share the cohort's job tests.
"""

import glob

import pytest

from tests.jobs import all_logs, run_job
from tests.test_cohort import job_config


@pytest.mark.parametrize("steps_per_dispatch", [1, 2])
def test_cohort_evaluation_only_job(tmp_path, steps_per_dispatch):
    """evaluation_only in cohort mode: eval tasks stream through every
    process's eval path (per-batch eval_step, or the grouped eval_many
    collective scan with --steps_per_dispatch), metric states merge
    master-side, AUC comes back."""
    cfg = job_config(
        tmp_path,
        job_type="evaluation_only",
        validation_data="synthetic://criteo?n=512&shards=2",
        records_per_task=256,
        steps_per_dispatch=steps_per_dispatch,
    )
    master, _, counts = run_job(cfg, tmp_path)
    assert counts["failed_permanently"] == 0
    results = master.evaluation.latest_results()
    assert "auc" in results and "loss" in results, results


@pytest.mark.parametrize("num_processes,steps_per_dispatch",
                         [(1, 1), (1, 2), (2, 1), (2, 2)])
def test_cohort_prediction_job(tmp_path, num_processes, steps_per_dispatch):
    """Prediction jobs end-to-end in BOTH worker flavors. Cohort mode was a
    round-3 gap (_data_service only knew train/eval, so prediction-only
    with num_processes>1 crashed): every process runs predict_step on the
    global batch, outputs allgather to the leader, and the zoo's
    prediction_outputs_processor writes them — exactly once across the
    job. num_processes=1 drives the plain worker's prediction path through
    the same harness; (1, 2) covers its grouped predict_many dispatch."""
    import numpy as np

    out_dir = tmp_path / "preds"
    cfg = job_config(
        tmp_path,
        job_type="prediction_only",
        prediction_data="synthetic://criteo?n=512&shards=2",
        records_per_task=256,
        num_processes=num_processes,
        steps_per_dispatch=steps_per_dispatch,
    )
    *_, counts = run_job(
        cfg, tmp_path, extra_env={"EDL_PREDICT_OUT": str(out_dir)})
    assert counts["failed_permanently"] == 0
    files = sorted(glob.glob(str(out_dir / "*.npy")))
    assert files, all_logs(tmp_path)[-2000:]
    total = sum(np.load(f).shape[0] for f in files)
    assert total == 512  # every record predicted exactly once, none padded
