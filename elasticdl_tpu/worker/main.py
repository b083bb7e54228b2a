"""Worker entrypoint.

Reference parity: elasticdl/python/worker/main.py — parse the re-serialized
argv the master/launcher passed, build the Worker, run the task loop.
"""

from __future__ import annotations

import time

_ENTERED = time.time()      # before the imports: `start.process` without /proc

import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
from typing import List, Optional  # noqa: E402

from elasticdl_tpu.common import membership_signal  # noqa: E402
from elasticdl_tpu.common.config import JobConfig  # noqa: E402
from elasticdl_tpu.observability import tracing  # noqa: E402
from elasticdl_tpu.worker.worker import Worker  # noqa: E402


def main(argv: Optional[List[str]] = None) -> int:
    # start-up joins the trace the master announced (a job's start, a
    # reform), where the membership signal file reaches this process
    tracing.mark_entry(_ENTERED)
    tracing.join_startup_trace(membership_signal.trace_id())
    # the process's start to here: interpreter and imports
    tracing.record_start("process", since=tracing.process_start_ts())
    cfg = JobConfig.from_argv(sys.argv[1:] if argv is None else argv)
    # EDL_PROCESS_ID marks a cohort member even when dynamic resizing has
    # shrunk the world to 1 process (cfg.num_processes is the ORIGINAL size)
    if cfg.num_processes > 1 or "EDL_PROCESS_ID" in os.environ:
        # SPMD cohort member. SIGTERM: the leader drains collectively
        # (finish the in-flight task, broadcast OP_ABORT|FLAG_CHECKPOINT,
        # every process joins one final save, exit EX_TEMPFAIL); a follower
        # cannot drain — it exits EX_TEMPFAIL immediately and the manager
        # relaunches the cohort from the last checkpoint. All the signal
        # wiring lives in run_cohort/CohortWorker (worker/cohort.py).
        from elasticdl_tpu.worker.cohort import run_cohort

        return run_cohort(cfg)
    worker = Worker(cfg)
    # k8s preemption delivers SIGTERM with a grace period; drain + checkpoint
    signal.signal(signal.SIGTERM, lambda *_: worker.preempt())
    return worker.run()


if __name__ == "__main__":
    raise SystemExit(main())
