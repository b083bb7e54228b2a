"""Multi-process SPMD cohort (worker/cohort.py): real subprocesses forming
one jax.distributed world over local CPU devices, driven by the in-process
master — the rebuild of the reference's elastic-AllReduce integration tests
(SURVEY §3.4/§4), including the kill-a-member fault injection.
"""

import os
import time

import pytest

from elasticdl_tpu.client.local import free_port
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.master.main import Master
from elasticdl_tpu.master.process_manager import ProcessManager
from tests.conftest import heavy_on_cpu
from tests.jobs import HERMETIC_ENV, all_logs, run_job


def job_config(tmp_path, **overrides):
    base = dict(
        job_name="cohort-e2e",
        model_zoo=os.path.abspath("model_zoo"),
        model_def="deepfm.deepfm.custom_model",
        model_params={"field_vocab": 64, "hidden": "16,16"},
        training_data="synthetic://criteo?n=2048&shards=4",
        records_per_task=512,
        minibatch_size=64,
        num_epochs=1,
        evaluation_steps=0,
        num_workers=1,
        num_processes=2,
        master_addr=f"localhost:{free_port()}",
        worker_heartbeat_s=1.0,
        task_timeout_s=300.0,
        shuffle=False,
    )
    base.update(overrides)
    return JobConfig(**base)


def test_cohort_job_end_to_end(tmp_path):
    cfg = job_config(tmp_path, output=str(tmp_path / "export"))
    *_, counts = run_job(cfg, tmp_path)
    assert counts["finished_training"] == 4
    assert counts["failed_permanently"] == 0
    log = all_logs(tmp_path)
    assert "distributed world v0 up: process 0/2" in log
    assert "distributed world v0 up: process 1/2" in log
    assert os.path.exists(tmp_path / "export" / "params.msgpack")


def test_cohort_grouped_dispatch_end_to_end(tmp_path):
    """--steps_per_dispatch=2 in COHORT mode: both processes run the same
    train_many scan over the stacked global batch (one collective dispatch
    per 2 minibatches); a 512-record task at minibatch 64 = 8 batches = 4
    full groups; task accounting and loss reporting unchanged."""
    cfg = job_config(tmp_path, steps_per_dispatch=2, wire_dtype="bfloat16")
    *_, counts = run_job(cfg, tmp_path)
    assert counts["finished_training"] == 4
    assert counts["failed_permanently"] == 0
    log = all_logs(tmp_path)
    assert "distributed world v0 up: process 0/2" in log
    assert "distributed world v0 up: process 1/2" in log


@pytest.mark.parametrize("num_processes", [
    1, pytest.param(2, marks=heavy_on_cpu),
])
def test_master_lr_push_applies(tmp_path, num_processes):
    """ReduceLROnPlateau's transport, end-to-end in both worker flavors:
    the master sets an LR override; a heartbeat carries it to the worker
    (plain mode, applied at the next task boundary) or to the cohort
    leader, then the ctrl broadcast (float64 bits in int32 halves) to
    every process, which all apply it at the same boundary."""
    cfg = job_config(tmp_path, num_processes=num_processes)
    fired = {"done": False}

    def push_lr(master, manager):
        # once the job is visibly underway, push the override
        if not fired["done"] and master.dispatcher.counts()["doing"] > 0:
            master.servicer.set_learning_rate(5e-4)
            fired["done"] = True

    *_, counts = run_job(cfg, tmp_path, observer=push_lr)
    assert counts["failed_permanently"] == 0
    assert fired["done"]
    log = all_logs(tmp_path)
    if num_processes == 2:
        # both cohort processes applied it (one log line per process)
        assert log.count("applied master-pushed LR 0.0005") == 2, log[-2000:]
    else:
        assert "runtime LR set to 0.0005" in log, log[-2000:]


@heavy_on_cpu
def test_cohort_member_kill_relaunches_and_resumes(tmp_path):
    cfg = job_config(
        tmp_path,
        training_data="synthetic://criteo?n=8192&shards=8",
        records_per_task=1024,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=8,
    )

    def kill_follower_after_checkpoint(master, manager):
        # wait until a checkpoint generation exists, then SIGKILL process 1
        if master.dispatcher.counts()["finished_training"] < 2:
            return False
        wp = manager._procs.get(1)
        if wp is None or wp.proc.poll() is not None:
            return False
        wp.proc.kill()
        return True

    *_, counts = run_job(cfg, tmp_path, mid_job=kill_follower_after_checkpoint)
    assert counts["finished_training"] == 8
    assert counts["failed_permanently"] == 0
    log = all_logs(tmp_path)
    assert "cohort resumed from checkpoint at step" in log, log[-3000:]


def test_cohort_leader_sigterm_drains_via_checkpoint(tmp_path):
    """Planned preemption (SIGTERM to the LEADER): instead of dying with
    work since the last interval checkpoint lost, the leader broadcasts
    OP_ABORT|FLAG_CHECKPOINT — a collective save every process joins — and
    the relaunched cohort resumes at exactly the pre-kill step. Interval
    checkpoints are disabled (checkpoint_steps=0) so the ONLY checkpoint on
    disk is the drain's: resuming from it proves the drain worked."""
    import re

    cfg = job_config(
        tmp_path,
        training_data="synthetic://criteo?n=8192&shards=8",
        records_per_task=1024,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=0,   # no interval saves: drain is the only source
    )

    lat = {}  # drain-latency instrumentation (BASELINE.md round log)

    def sigterm_leader(master, manager):
        if master.dispatcher.counts()["finished_training"] < 2:
            return False
        wp = manager._procs.get(0)
        if wp is None or wp.proc.poll() is not None:
            return False
        wp.proc.terminate()   # SIGTERM: the k8s-preemption shape
        lat["sigterm_t"] = time.monotonic()   # as reformation_log stamps
        return True

    def observe(master, manager):
        if "sigterm_t" in lat and "reform_t" not in lat and \
                manager.reformation_log:
            lat["reform_t"] = manager.reformation_log[0][0]

    *_, counts = run_job(cfg, tmp_path, mid_job=sigterm_leader, observer=observe)
    assert counts["finished_training"] == 8
    assert counts["failed_permanently"] == 0
    log = all_logs(tmp_path)
    assert "leader preempted: draining cohort via collective checkpoint" in log
    saved = re.search(r"preemption checkpoint saved at step (\d+)", log)
    resumed = re.search(r"cohort resumed from checkpoint at step (\d+)", log)
    assert saved and resumed, log[-3000:]
    # the restored step IS the pre-kill step: nothing trained was redone
    assert resumed.group(1) == saved.group(1), (saved.group(), resumed.group())
    drain_s = lat.get("reform_t", time.monotonic()) - lat["sigterm_t"]
    print(f"\n[preemption-drain] SIGTERM -> drained+torn-down {drain_s:.2f}s "
          f"(bounded by the in-flight task + collective save)")


def test_cohort_lease_aborts_when_master_lost(tmp_path):
    """Leader unit test for orphan cleanup: once no master RPC has
    succeeded for master_unreachable_timeout_s, the next lease becomes
    OP_ABORT (taking the whole cohort down EX_TEMPFAIL) instead of NOOP
    retries forever — a cohort whose master's process tree died must not
    survive it indefinitely."""
    from elasticdl_tpu.parallel.elastic import CohortContext
    from elasticdl_tpu.worker.cohort import (
        FLAG_CHECKPOINT,
        OP_ABORT,
        OP_NOOP,
        CohortWorker,
    )

    cfg = job_config(tmp_path, master_unreachable_timeout_s=5.0)

    class DeadStub:
        def GetTask(self, *a, **k):
            raise ConnectionError("connection refused")

    w = CohortWorker(cfg, ctx=CohortContext("localhost:1", 2, 0))
    w._session.stub = DeadStub()
    # master answered recently: failures are still transient -> NOOP
    w._session.last_master_ok = time.monotonic()
    assert w._lease_control()[0] == OP_NOOP
    assert not w._shutdown.is_set()
    # silent past the limit -> ABORT with a final collective checkpoint
    # (clean task boundary, the save needs no master), shutdown latched
    w._session.last_master_ok = time.monotonic() - 6.0
    ctrl = w._lease_control()
    assert ctrl[0] == OP_ABORT and ctrl[6] & FLAG_CHECKPOINT
    assert w._shutdown.is_set() and w._session.master_lost
    # the heartbeat thread can be the one that crosses the limit (mid-task);
    # the ensuing shutdown-branch lease must carry the same checkpoint flag
    ctrl = w._lease_control()
    assert ctrl[0] == OP_ABORT and ctrl[6] & FLAG_CHECKPOINT


def test_cohort_aborts_itself_when_master_vanishes(tmp_path):
    """Orphan cleanup end-to-end: the master's gRPC server cold-stops (no
    shutdown flag ever reaches the leader); after
    master_unreachable_timeout_s the leader must broadcast the abort and
    BOTH real subprocesses must exit on their own — no cohort may outlive
    its master indefinitely (observed pre-fix: orphans surviving hours)."""
    cfg = job_config(
        tmp_path,
        training_data="synthetic://criteo?n=8192&shards=8",
        records_per_task=1024,
        master_unreachable_timeout_s=6.0,
        relaunch_max=0,
    )
    master = Master(cfg)
    manager = ProcessManager(
        cfg,
        membership=master.membership,
        extra_env=HERMETIC_ENV,
        log_dir=str(tmp_path / "logs"),
        job_finished_fn=master.dispatcher.finished,
    )
    master.start()
    manager.start_workers()
    try:
        deadline = time.time() + 180
        while (
            time.time() < deadline
            and master.dispatcher.counts()["finished_training"] < 1
        ):
            master.membership.reap()
            master.dispatcher.poke()
            time.sleep(0.2)
        assert master.dispatcher.counts()["finished_training"] >= 1
        master.server.stop(grace=0)   # cold stop: master vanishes

        deadline = time.time() + 120
        while time.time() < deadline:
            procs = list(manager._procs.values())
            if procs and all(wp.proc.poll() is not None for wp in procs):
                break
            time.sleep(0.5)
        else:
            raise AssertionError(
                "cohort outlived its vanished master: "
                + all_logs(tmp_path)[-3000:]
            )
        log = all_logs(tmp_path)
        assert "master presumed gone, aborting cohort" in log, log[-3000:]
    finally:
        master.server.stop(grace=0)
        manager.stop()


@heavy_on_cpu
def test_cohort_resizes_down_at_exhausted_budget(tmp_path):
    """Dynamic world resizing, scale-in: a member dies with the relaunch
    budget already spent — instead of stalling/failing, the cohort re-forms
    at N-1 and finishes the job with exactly-once task accounting
    (SURVEY §2.1 rendezvous re-formation at a new world size)."""
    cfg = job_config(
        tmp_path,
        training_data="synthetic://criteo?n=8192&shards=8",
        records_per_task=1024,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=8,
        relaunch_max=0,  # budget spent from the start: loss must resize
    )
    lat = {}  # re-formation latency instrumentation (BASELINE.md round log)

    def kill_follower(master, manager):
        if master.dispatcher.counts()["finished_training"] < 2:
            return False
        wp = manager._procs.get(1)
        if wp is None or wp.proc.poll() is not None:
            return False
        wp.proc.kill()
        # monotonic, like the manager's reformation_log stamps
        lat["kill_t"] = time.monotonic()
        lat["tasks_at_kill"] = master.dispatcher.counts()["finished_training"]
        return True

    def observe(master, manager):
        if "kill_t" not in lat or "first_task_t" in lat:
            return
        if not manager.reformation_log:
            return
        lat.setdefault("reform_t", manager.reformation_log[0][0])
        if (
            master.dispatcher.counts()["finished_training"]
            > lat["tasks_at_kill"]
        ):
            lat["first_task_t"] = time.monotonic()

    master, manager, counts = run_job(
        cfg, tmp_path, mid_job=kill_follower, observer=observe,
    )
    assert counts["finished_training"] == 8
    assert counts["failed_permanently"] == 0
    assert manager.cohort_size == 1
    # one re-formation, from 2 to 1 processes
    assert [(o, n) for _, o, n in manager.reformation_log] == [(2, 1)]
    log = all_logs(tmp_path)
    assert "up: process 0/1" in log  # the new one-process world formed
    assert "cohort resumed from checkpoint at step" in log
    # kill -> teardown decision, and kill -> first task completed at the new
    # size (world re-form + checkpoint restore + one task's work); printed so
    # runs feed BASELINE.md's re-formation latency row
    detect_s = lat["reform_t"] - lat["kill_t"]
    recover_s = lat["first_task_t"] - lat["kill_t"]
    assert 0 <= detect_s < 60 and 0 < recover_s < 300
    print(
        f"\n[reformation-latency] kill->teardown {detect_s:.2f}s, "
        f"kill->first-task-at-new-size {recover_s:.2f}s"
    )


@heavy_on_cpu
def test_cohort_scales_up_on_add_worker(tmp_path):
    """Dynamic world resizing, scale-out: add_worker mid-job re-forms the
    cohort at N+1 (fresh coordinator, new world version, checkpoint restore)
    and the job completes with all tasks accounted for."""
    cfg = job_config(
        tmp_path,
        # long enough that the quiesce + re-formation land MID-job (the
        # pre-teardown checkpoint wait added in round 3 means a planned
        # resize takes a few extra seconds; an 8-task job could finish first)
        training_data="synthetic://criteo?n=24576&shards=24",
        records_per_task=1024,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=8,
    )

    def scale_up(master, manager):
        if master.dispatcher.counts()["finished_training"] < 2:
            return False
        assert manager.add_worker() == 3
        return True

    master, manager, counts = run_job(
        cfg, tmp_path, mid_job=scale_up
    )
    assert counts["finished_training"] == 24
    assert counts["failed_permanently"] == 0
    assert manager.cohort_size == 3
    assert [(o, n) for _, o, n in manager.reformation_log] == [(2, 3)]
    log = all_logs(tmp_path)
    assert "up: process 2/3" in log  # the third member joined the new world


@heavy_on_cpu
def test_cohort_remove_worker_quiesces_then_resizes(tmp_path):
    """Operator scale-in (round-3, VERDICT #7): remove_worker triggers a
    PRE-TEARDOWN checkpoint (via the heartbeat should_checkpoint bit +
    FLAG_CHECKPOINT control broadcast) before re-forming at N-1, so a
    planned resize redoes at most sub-task progress. checkpoint_steps is set
    beyond the job so the ONLY possible checkpoint is the quiesce one —
    'resumed from checkpoint' in the logs proves it landed."""
    cfg = job_config(
        tmp_path,
        # long enough that the quiesce + re-formation happen MID-job (a
        # 2-process CPU world finishes ~1024 records/s-ish; 8 tasks was over
        # before the resize landed)
        training_data="synthetic://criteo?n=24576&shards=24",
        records_per_task=1024,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=100000,   # interval checkpointing never fires
    )

    def scale_down(master, manager):
        if master.dispatcher.counts()["finished_training"] < 2:
            return False
        assert manager.remove_worker() == 1
        return True

    master, manager, counts = run_job(
        cfg, tmp_path, mid_job=scale_down
    )
    assert counts["finished_training"] == 24
    assert counts["failed_permanently"] == 0
    assert manager.cohort_size == 1
    assert [(o, n) for _, o, n in manager.reformation_log] == [(2, 1)]
    log = all_logs(tmp_path)
    assert "up: process 0/1" in log
    # the quiesce checkpoint was written BEFORE teardown and restored after
    assert "cohort resumed from checkpoint at step" in log, log[-3000:]
