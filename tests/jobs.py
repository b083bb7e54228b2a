"""The one harness for tests that run a job: an in-process master plus real
worker subprocesses under the ProcessManager, wired as client/local.py wires
them, polled until the job is finished OR can no longer finish.

The children's environment is the parent's plus HERMETIC_ENV and the
caller's `extra_env` — deliberately not scrubbed: a variable a test leaked
into os.environ must show (tests/conftest.py fails the test that leaked it),
not be papered over here.
"""

import dataclasses
import glob
import time

from elasticdl_tpu.master.main import Master
from elasticdl_tpu.master.process_manager import ProcessManager

HERMETIC_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
    "EDL_LOG_LEVEL": "INFO",
}


def all_logs(tmp_path) -> str:
    out = []
    for f in sorted(glob.glob(str(tmp_path / "logs" / "*.log"))):
        out.append(open(f, errors="replace").read())
    return "\n".join(out)


def patient_master(cfg, reap_after_s=90.0) -> Master:
    """A master whose reaper waits `reap_after_s` for a heartbeat whatever
    cadence `cfg` gives the workers: a worker's first compile beside five
    other xdist workers outlasts the 1-3 s that three test-sized heartbeats
    make, and a worker reaped while it compiles fails a case that is about
    something else. The workers keep `cfg`'s own heartbeat."""
    return Master(dataclasses.replace(cfg, worker_heartbeat_s=reap_after_s / 3))


def run_job(cfg, tmp_path, *, mid_job=None, observer=None, timeout_s=420,
            extra_env=None, master_of=Master):
    """Run `cfg` to completion; returns (master, manager, counts), both
    already shut down.

    `mid_job(master, manager) -> bool` is polled until it returns True (the
    fault to inject once, and it must get injected); `observer(master,
    manager)` is polled throughout.
    Raises AssertionError, with the dispatcher's counts and the tail of the
    worker logs, as soon as every worker is dead with its relaunch budget
    spent — a dead job costs its launches, not the deadline — or, as the
    last resort, at `timeout_s`. `master_of(cfg)` builds the master
    (`patient_master` where no case of the test needs the reaper)."""
    master = master_of(cfg)
    manager = ProcessManager(
        cfg,
        membership=master.membership,
        extra_env={**HERMETIC_ENV, **(extra_env or {})},
        log_dir=str(tmp_path / "logs"),
        job_finished_fn=master.dispatcher.finished,
        # planned resizes quiesce via the heartbeat should_checkpoint bit
        checkpoint_request_fn=lambda: master.servicer.request_checkpoint(0),
    )
    master.start()
    manager.start_workers()
    try:
        deadline = time.time() + timeout_s
        fired = False
        while not master.dispatcher.finished():
            master.membership.reap()
            master.dispatcher.poke()
            if mid_job is not None and not fired:
                fired = mid_job(master, manager)
            if observer is not None:
                observer(master, manager)
            dead = manager.all_failed()
            if dead or time.time() > deadline:
                why = ("every worker failed for good" if dead
                       else f"job not finished after {timeout_s} s")
                raise AssertionError(
                    f"{why}: {master.dispatcher.counts()}\n"
                    + all_logs(tmp_path)[-3000:]
                )
            time.sleep(0.2)
        assert mid_job is None or fired, "job finished before mid_job fired"
        return master, manager, master.dispatcher.counts()
    finally:
        master.shutdown(grace_s=2)
        manager.stop()
