"""End-to-end: in-process master + real worker subprocesses training
synthetic MNIST over gRPC — the minimum slice of SURVEY §7, as a test.

Mirrors the reference's minikube integration tests (SURVEY §4) at process
granularity: real process boundaries, real wire traffic, no mocks.
"""

import os
import time

import pytest

from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.client.local import free_port
from tests.jobs import run_job


def job_config(tmp_path, num_workers=1, **overrides):
    base = dict(
        job_name="e2e",
        model_zoo=os.path.abspath("model_zoo"),
        model_def="mnist.mnist_cnn.custom_model",
        model_params={"learning_rate": 0.01},
        training_data="synthetic://mnist?n=400&shards=4",
        validation_data="synthetic://mnist?n=96&shards=2",
        records_per_task=100,
        minibatch_size=32,
        num_epochs=1,
        evaluation_steps=0,           # eval at epoch end
        num_workers=num_workers,
        master_addr=f"localhost:{free_port()}",
        worker_heartbeat_s=1.0,
        task_timeout_s=120.0,
        shuffle=False,
    )
    base.update(overrides)
    return JobConfig(**base)


def test_local_job_end_to_end(tmp_path):
    cfg = job_config(tmp_path, num_workers=1)
    master, manager, counts = run_job(cfg, tmp_path)
    assert counts["finished_training"] == 4      # 400 records / 100 per task
    assert counts["failed_permanently"] == 0
    # epoch-end eval ran and aggregated
    results = master.evaluation.latest_results()
    assert "accuracy" in results and "loss" in results, results
    assert master.servicer.mean_training_loss() is not None
    # workers exited cleanly on job completion
    deadline = time.time() + 30
    while not manager.all_exited() and time.time() < deadline:
        time.sleep(0.5)
    assert manager.all_exited()


def test_local_job_with_grouped_dispatch(tmp_path):
    """--steps_per_dispatch=2: the worker runs batch groups through
    train_many (one XLA dispatch per 2 minibatches) and the job completes
    with identical task accounting — 100-record tasks at minibatch 32 leave
    a 4-batch task = 2 full groups, exercising group flush + the
    partial-group fallback on the final 4-record batch... (4 batches: 32,32,
    32,4 → one full group + one partial)."""
    cfg = job_config(tmp_path, num_workers=1, steps_per_dispatch=2,
                     wire_dtype="bfloat16")  # grouped path must honor the cast
    master, _, counts = run_job(cfg, tmp_path)
    assert counts["finished_training"] == 4
    assert counts["failed_permanently"] == 0
    # all 400 records were applied exactly once (grouped accounting)
    assert master.servicer.mean_training_loss() is not None
    results = master.evaluation.latest_results()
    assert "accuracy" in results, results


@pytest.mark.slow
def test_profiling_and_step_time_summaries(tmp_path):
    """Round-3 observability (SURVEY §5 tracing): --profile_dir produces
    jax.profiler trace files, and the master's train summary stream carries
    per-step wall time alongside loss.

    Marked slow for its ~20 s (measured on jax 0.9.0, PR 21): tier-1 runs
    close to its time limit."""
    cfg = job_config(
        tmp_path,
        profile_dir=str(tmp_path / "profile"),
        profile_start_step=2,
        profile_steps=4,
        summary_dir=str(tmp_path / "summaries"),
        job_type="training_only",
    )
    run_job(cfg, tmp_path)

    # trace files appeared (jax.profiler writes plugins/profile/<ts>/...)
    trace_files = []
    for root, _dirs, files in os.walk(tmp_path / "profile"):
        trace_files += [os.path.join(root, f) for f in files]
    assert trace_files, "profile_dir is empty — no trace was written"

    # the train summary stream has step_time_ms on every loss line
    import json

    events_path = tmp_path / "summaries" / "train" / "events.jsonl"
    lines = [
        json.loads(l) for l in events_path.read_text().splitlines() if l.strip()
    ]
    assert lines, "no train summaries written"
    assert all("step_time_ms" in rec and rec["step_time_ms"] > 0 for rec in lines)
    assert all("loss" in rec for rec in lines)


def test_run_job_stops_when_the_job_is_dead(tmp_path):
    """The harness itself (tests/jobs.py): a one-process worker started as
    cohort member 2 of 1 dies at world formation on every launch. run_job
    must raise once the relaunch budget is spent — about four launches —
    with the worker's own words, not wait out its 420 s deadline."""
    cfg = job_config(tmp_path)
    t0 = time.time()
    with pytest.raises(AssertionError, match="world formation failed"):
        run_job(cfg, tmp_path, extra_env={"EDL_PROCESS_ID": "2"})
    assert time.time() - t0 < 90


# the start-up spans of the table in docs/observability.md, by the process
# that records them
LAUNCHER_SPANS = {"start.launch", "start.master", "start.spawn"}
WORKER_SPANS = {"start.process", "start.connect", "start.backend",
                "start.trainer", "start.first_task", "start.state", "compile"}


def test_every_process_of_a_job_prints_its_start_up_ledger_once(tmp_path):
    """The whole job as a user starts it — launcher (and master) in one
    process, one worker in another: each prints one `start-up ledger:` line,
    together they name every start-up span once, the worker's joins the
    launcher's trace, and the parts lie in order on the wall clock."""
    import json
    import subprocess
    import sys

    from tests.jobs import HERMETIC_ENV

    argv = [
        sys.executable, "-m", "elasticdl_tpu.client.main", "train",
        "--model_zoo", os.path.abspath("model_zoo"),
        "--model_def", "deepfm.deepfm.custom_model",
        "--model_params", "field_vocab=64;hidden=16,16",
        "--minibatch_size", "64", "--steps_per_dispatch", "4",
        "--training_data", "synthetic://criteo?n=1024&shards=2",
        "--records_per_task", "512", "--master_addr", "localhost:0",
        # where the membership signal file lives: how the trace id travels
        "--checkpoint_dir", str(tmp_path / "ckpt"),
    ]
    proc = subprocess.run(
        argv, env={**os.environ, **HERMETIC_ENV}, capture_output=True,
        text=True, timeout=300)
    log = proc.stdout + proc.stderr
    assert proc.returncode == 0, log[-3000:]
    marker = "start-up ledger: "
    ledgers = [json.loads(line.split(marker, 1)[1])
               for line in log.splitlines() if marker in line]
    assert len(ledgers) == 2 and len({l["pid"] for l in ledgers}) == 2
    launcher, worker = ledgers
    assert set(launcher["spans"]) == LAUNCHER_SPANS
    assert set(worker["spans"]) == WORKER_SPANS
    assert (launcher["role"], worker["role"]) == ("master", "worker-0")
    assert all(span["n"] == 1 for ledger in ledgers
               for span in ledger["spans"].values())
    assert worker["trace_id"] == launcher["trace_id"]

    def interval(ledger, name):
        span = ledger["spans"][name]
        return span["ts"], span["ts"] + span["s"]

    # launcher up, then the spawn, inside it the worker's interpreter and
    # imports and its registration, then backend, trainer and the first task
    order = [interval(launcher, "start.launch"), interval(worker, "start.process"),
             interval(worker, "start.connect"), interval(worker, "start.backend"),
             interval(worker, "start.trainer"), interval(worker, "start.first_task")]
    # (the kernel stamps a process's start to the clock tick, 10 ms)
    assert all(a[1] <= b[0] + 0.02 for a, b in zip(order, order[1:])), order
    spawn = interval(launcher, "start.spawn")
    assert spawn[0] <= order[1][0] + 0.05 and order[2][1] <= spawn[1] + 0.05
    assert interval(launcher, "start.master")[1] <= order[0][1] + 1e-3
    # state and compile are the first task's children: its own time is less
    first_task = worker["spans"]["start.first_task"]
    assert first_task["self_s"] < first_task["s"] - worker["spans"]["compile"]["s"]
    assert worker["spans"]["compile"]["each"][0]["program"] == "train_many"
    assert worker["named_s"] >= 0.95 * worker["wall_s"]
