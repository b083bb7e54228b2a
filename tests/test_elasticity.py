"""Fault injection: kill real worker processes mid-job and assert the job
still completes with exactly-once task accounting and checkpoint-based
resume. Mirrors the reference's integration scripts that `kubectl delete pod`
a worker mid-job (SURVEY §4 fault-tolerance tests), at process granularity.
"""

import os
import time


from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.master.main import Master
from elasticdl_tpu.master.process_manager import ProcessManager
from elasticdl_tpu.client.local import free_port
from tests.jobs import HERMETIC_ENV, all_logs, patient_master, run_job


def job_config(tmp_path, **overrides):
    base = dict(
        job_name="elastic",
        model_zoo=os.path.abspath("model_zoo"),
        model_def="mnist.mnist_cnn.custom_model",
        model_params={"learning_rate": 0.01},
        training_data="synthetic://mnist?n=600&shards=4",
        records_per_task=50,
        minibatch_size=32,
        num_epochs=1,
        num_workers=1,
        master_addr=f"localhost:{free_port()}",
        worker_heartbeat_s=1.0,
        task_timeout_s=180.0,
        relaunch_max=2,
        shuffle=False,
    )
    base.update(overrides)
    return JobConfig(**base)


def kill_after(n_tasks, **kill_kwargs):
    """A run_job `mid_job`: kill worker 0 (relaunching it) once `n_tasks`
    training tasks have finished."""
    def mid_job(master, manager):
        if master.dispatcher.counts()["finished_training"] < n_tasks:
            return False
        assert manager.kill_worker(0, relaunch=True, **kill_kwargs)
        return True

    return mid_job


def test_kill_worker_mid_job_recovers(tmp_path):
    cfg = job_config(tmp_path)
    *_, counts = run_job(cfg, tmp_path, mid_job=kill_after(2))
    # exactly-once accounting: 600 records / 50 per task = 12 tasks, no
    # double-completion, nothing lost
    assert counts["finished_training"] == 12, counts
    assert counts["failed_permanently"] == 0, counts
    assert counts["todo"] == 0 and counts["doing"] == 0, counts
    # the kill was detected and the lease recovered (or already reported):
    # the relaunched worker must have registered under the same id
    log = all_logs(tmp_path)
    assert log.count("registered as worker 0") >= 2, log[-2000:]


def test_killed_worker_resumes_from_checkpoint(tmp_path):
    cfg = job_config(
        tmp_path,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=2,
    )
    *_, counts = run_job(cfg, tmp_path, mid_job=kill_after(3))
    assert counts["finished_training"] == 12, counts
    assert counts["failed_permanently"] == 0, counts
    log = all_logs(tmp_path)
    assert "resumed from checkpoint at step" in log, (
        "relaunched worker did not restore:\n" + log[-4000:]
    )
    # checkpoints were written at interval steps
    steps = [int(d) for d in os.listdir(cfg.checkpoint_dir) if d.isdigit()]
    assert steps and max(steps) >= 2, steps


def test_relaunch_budget_exhaustion_fails_job(tmp_path):
    """A worker that is killed more times than relaunch_max stays down, and
    the master's abort hook reports the job as unrecoverable."""
    cfg = job_config(tmp_path, relaunch_max=0, task_timeout_s=15.0)
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
        while time.time() < deadline:
            if master.dispatcher.counts()["finished_training"] >= 1:
                break
            time.sleep(0.2)
        assert manager.kill_worker(0, relaunch=True)
        # with relaunch_max=0 the watcher retires the worker instead of
        # respawning; the job can no longer make progress
        ok = master.wait(timeout_s=60, abort_fn=manager.all_failed)
        assert not ok
        assert manager.all_failed()
    finally:
        master.shutdown(grace_s=1)
        manager.stop()


def test_sigterm_preemption_checkpoints_and_resumes(tmp_path):
    """The k8s-preemption shape: SIGTERM mid-job → the worker drains the
    current batch, force-saves a checkpoint, exits EX_TEMPFAIL; the watcher
    relaunches it and it resumes from that checkpoint even with no interval
    checkpointing configured."""
    cfg = job_config(
        tmp_path,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=0,          # only the preemption save writes
    )
    # the watcher sees the exit and relaunches: nothing here is the reaper's
    *_, counts = run_job(cfg, tmp_path, mid_job=kill_after(2, graceful=True),
                         master_of=patient_master)
    assert counts["finished_training"] == 12, counts
    assert counts["failed_permanently"] == 0, counts
    log = all_logs(tmp_path)
    assert "preemption signal received" in log, log[-2000:]
    assert "resumed from checkpoint at step" in log, log[-4000:]


def test_worker_exits_when_master_vanishes(tmp_path):
    """Orphan cleanup: the master's process dies WITHOUT a graceful shutdown
    heartbeat (grpc server stopped cold, request_shutdown never sent). The
    worker must not spin on the dead address forever — after
    master_unreachable_timeout_s with no successful RPC it exits
    EX_TEMPFAIL. (Observed pre-fix: worker processes surviving hours after
    their master's tree was SIGKILLed.)"""
    import threading

    from elasticdl_tpu.worker.worker import Worker

    cfg = job_config(
        tmp_path,
        worker_heartbeat_s=0.3,
        master_unreachable_timeout_s=4.0,
    )
    # the worker beats every 0.3 s so that it notices a vanished master in
    # seconds; this master, which only has to hand out a task and vanish,
    # must not reap it while its first step compiles
    master = patient_master(cfg)
    master.start()
    worker = Worker(cfg)
    rc = {}
    t = threading.Thread(target=lambda: rc.update(v=worker.run()), daemon=True)
    try:
        t.start()
        deadline = time.time() + 120
        while (
            time.time() < deadline
            and master.dispatcher.counts()["finished_training"] < 1
        ):
            master.membership.reap()
            master.dispatcher.poke()
            time.sleep(0.1)
        assert master.dispatcher.counts()["finished_training"] >= 1
        # cold stop: no shutdown flag ever reaches the worker
        master.server.stop(grace=0)
        t.join(timeout=90)
        assert not t.is_alive(), "worker did not exit after master vanished"
        assert rc["v"] == 75, rc
    finally:
        master.server.stop(grace=0)


def test_relaunch_reuses_compilation_cache(tmp_path):
    """--compilation_cache_dir: the killed worker's relaunch deserializes
    the previous generation's XLA executables instead of recompiling (on a
    real TPU that is 20-40 s off every elastic recovery). The HIT is what's
    asserted: the entry set is snapshotted at kill time (generation 1 has
    compiled its whole train path by then) and must NOT materially grow —
    a change that makes cache keys generation-dependent (world version or a
    per-launch seed leaking into the compilation key) would near-double it
    and is the exact regression this feature exists to prevent."""
    cache_dir = tmp_path / "xla-cache"
    cfg = job_config(
        tmp_path,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=4,
        compilation_cache_dir=str(cache_dir),
        compilation_cache_min_compile_s=0.0,   # test-sized programs cache
    )
    at_kill = {}

    def snapshot_then_kill(master, manager):
        if master.dispatcher.counts()["finished_training"] < 2:
            return False
        at_kill["entries"] = set(os.listdir(cache_dir))
        assert manager.kill_worker(0, relaunch=True)
        return True

    # an inherited JAX_COMPILATION_CACHE_DIR would win over the flag under
    # test (common/runtime.py); empty reads as unset
    run_job(cfg, tmp_path, mid_job=snapshot_then_kill,
            extra_env={"JAX_COMPILATION_CACHE_DIR": ""})
    entries_at_kill = at_kill.get("entries")
    assert entries_at_kill, "cache empty at kill: nothing compiled?"
    log = all_logs(tmp_path)
    assert "persistent XLA compilation cache" in log
    final = set(os.listdir(cache_dir))
    # The relaunched generation legitimately compiles utility programs the
    # first never ran (orbax restore-path slices etc.) — the program that
    # matters is the train step (`step_fn`, the 20-40 s compile on real
    # TPU). Entry names are `jit_<name>-<key hash>-cache`: a SECOND
    # jit_step_fn entry after the relaunch means the cache key became
    # generation-dependent and the relaunch recompiled — the exact
    # regression this feature exists to prevent.
    def step_entries(entries):
        return {e for e in entries if e.startswith("jit_step_fn-")}

    assert step_entries(entries_at_kill), (
        "no train-step cache entry at kill time", entries_at_kill)
    assert step_entries(final) == step_entries(entries_at_kill), (
        "relaunch produced a new train-step cache key",
        step_entries(final) - step_entries(entries_at_kill),
    )
