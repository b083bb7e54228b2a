"""Fault injection: kill real worker processes mid-job and assert the job
still completes with exactly-once task accounting and checkpoint-based
resume. Mirrors the reference's integration scripts that `kubectl delete pod`
a worker mid-job (SURVEY §4 fault-tolerance tests), at process granularity.
"""

import os
import time

import pytest

from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.master.main import Master
from elasticdl_tpu.master.process_manager import ProcessManager
from elasticdl_tpu.client.local import free_port
from tests.conftest import listening
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


# ---------------------------------------------------------------------- #
# a live worker the master wrote off (ROADMAP C21), and the watcher's last
# pass (C19)


def test_a_reaper_that_fires_on_a_live_worker_costs_no_task(tmp_path):
    """The worker's heartbeats lapse while it is alive (a long compile
    beside five other processes; here the reaper's verdict is simply
    delivered) and the membership writes it off. Its next heartbeat is
    told to re-register, not to shut down: the SAME process goes on, the
    leases the reaper requeued run once, and the job ends. Before the
    repair the worker obeyed `shutdown`, left with exit 0, was booked
    SUCCEEDED and the job waited for nobody until the deadline."""
    # beats every 0.3 s and two dozen tasks: the write-off after the first
    # task leaves the job seconds — many beats — to go
    cfg = job_config(tmp_path, worker_heartbeat_s=0.3,
                     training_data="synthetic://mnist?n=1200&shards=4")

    def write_off(master, manager):
        if master.dispatcher.counts()["finished_training"] < 1:
            return False
        assert master.membership.mark_dead(0, reason="heartbeat timeout")
        return True

    # a patient reaper, so that the ONE verdict is this test's
    *_, counts = run_job(cfg, tmp_path, mid_job=write_off,
                         master_of=patient_master)
    assert counts["finished_training"] == 24, counts
    assert counts["failed_permanently"] == 0, counts
    assert counts["todo"] == 0 and counts["doing"] == 0, counts
    log = all_logs(tmp_path)
    assert "re-registered with restarted master as worker 0" in log, log[-3000:]
    # repaired in place: no second process was needed
    assert log.count("registered as worker 0") == 1, log[-3000:]


class _FakeProc:
    """A `Popen` whose exit the test decides."""

    pid = 4242

    def __init__(self, code=None):
        self.code = code
        self.polls = 0

    def poll(self):
        self.polls += 1
        return self.code

    def terminate(self):
        self.code = -15 if self.code is None else self.code

    kill = terminate

    def wait(self, timeout=None):
        return self.code


class _Deaths:
    def __init__(self):
        self.dead = []

    def add_join_callback(self, cb):
        pass

    def mark_dead(self, wid, reason=""):
        self.dead.append((wid, reason))
        return True


def _fake_manager(monkeypatch, procs, finished, num_processes=1):
    """A ProcessManager over fake processes: `procs` are its first
    generation, every later `_spawn` gives a running one."""
    from elasticdl_tpu.master import process_manager as pm

    monkeypatch.setattr(
        pm.ProcessManager, "_spawn",
        lambda self, worker_id, relaunches=0, process_id=0: pm._WorkerProc(
            worker_id=worker_id, proc=_FakeProc(), relaunches=relaunches),
    )
    cfg = JobConfig(model_def="m.f", master_addr="localhost:1",
                    num_processes=num_processes, relaunch_max=2)
    mgr = pm.ProcessManager(
        cfg, membership=_Deaths(), membership_signal_path="",
        job_finished_fn=lambda: finished)
    for slot, proc in enumerate(procs):
        mgr._procs[slot] = pm._WorkerProc(worker_id=0, proc=proc)
    return mgr


def _watch(mgr, until, poll_s=0.01):
    """Run the watcher until `until()` holds, then stop the manager."""
    import threading

    mgr._watcher = threading.Thread(
        target=mgr._watch_loop, kwargs={"poll_s": poll_s}, daemon=True)
    mgr._watcher.start()
    deadline = time.time() + 10
    while not until() and mgr._watcher.is_alive() and time.time() < deadline:
        time.sleep(0.005)
    held = until()
    mgr.stop(grace_s=5)
    assert not mgr._watcher.is_alive()
    return held


@pytest.mark.parametrize("finished", [False, True])
def test_an_exit_0_is_a_success_only_after_the_job_s_end(
        monkeypatch, caplog, finished):
    from elasticdl_tpu.common.constants import PodStatus

    left = _FakeProc(code=0)
    mgr = _fake_manager(monkeypatch, [left], finished)
    first = mgr._procs[0]
    with listening(caplog, "elasticdl_tpu.master.process_manager"):
        assert _watch(mgr, lambda: (
            first.status == PodStatus.SUCCEEDED or mgr._procs[0] is not first))
    said = [r.getMessage() for r in caplog.records]
    if finished:
        assert first.status == PodStatus.SUCCEEDED and mgr._procs[0] is first
        assert "worker 0 exited cleanly" in said
        assert mgr._membership.dead == []
    else:
        # told to go with tasks left: a death like any other
        assert mgr._membership.dead == [(0, "exit code 0")]
        assert mgr._procs[0].relaunches == 1
        assert any("worker 0 died (code 0); relaunch 1/2" in m for m in said)
        assert "worker 0 exited cleanly" not in said


@pytest.mark.parametrize("finished", [False, True])
def test_a_cohort_that_all_left_with_0_is_done_only_after_the_job_s_end(
        monkeypatch, caplog, finished):
    mgr = _fake_manager(
        monkeypatch, [_FakeProc(code=0), _FakeProc(code=0)], finished,
        num_processes=2)
    with listening(caplog, "elasticdl_tpu.master.process_manager"):
        assert _watch(mgr, lambda: (
            bool(mgr.reformation_log) or not mgr._watcher.is_alive()))
    said = [r.getMessage() for r in caplog.records]
    if finished:
        assert "cohort exited, codes [0, 0]" in said
        assert not mgr.reformation_log and mgr._membership.dead == []
    else:
        assert "cohort exited, codes [0, 0]" not in said
        assert [(old, new) for _, old, new in mgr.reformation_log] == [(2, 2)]
        assert mgr._membership.dead == [(0, "cohort member(s) [0, 1] died")]


@pytest.mark.parametrize("num_processes,line", [
    (1, "worker 0 exited cleanly"), (2, "cohort exited, codes [0, 0]")])
def test_an_exit_between_the_last_poll_and_stop_is_still_booked(
        monkeypatch, caplog, num_processes, line):
    """ROADMAP C19: the launcher polls `all_exited()` every 0.2 s and then
    stops the manager, whose watcher polls every 0.5 s — an exit that lands
    between the watcher's last poll and `stop()` used to go unsaid."""
    from elasticdl_tpu.common.constants import PodStatus

    procs = [_FakeProc() for _ in range(num_processes)]
    mgr = _fake_manager(monkeypatch, procs, True, num_processes)
    with listening(caplog, "elasticdl_tpu.master.process_manager"):
        # the watcher has looked once (all running) and sleeps for an hour
        assert _watch(mgr, lambda: all(p.polls for p in procs) and [
            setattr(p, "code", 0) for p in procs], poll_s=3600)
    assert line in [r.getMessage() for r in caplog.records]
    assert all(wp.status == PodStatus.SUCCEEDED for wp in mgr._procs.values())
