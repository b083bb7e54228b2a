"""From the program's own start-up spans to the parts of `setup_s`.

The program records its start-up under `tracing.span`
(`elasticdl_tpu/observability/tracing.py`): `start.*` from a process's start
to the end of its first task's turn, `start.state` around
`Trainer.init_state`, `compile` around every compilation `Trainer` asks for
(with JAX's own trace / lower / backend / cache figures on it) and
`ckpt.restore`. `tracing.startup_ledger` folds one process's records into one
dict, and every process of a job prints its own once, as a
`start-up ledger: {...}` line.

A job cell: the lines are parsed out of the job's log, which the driver keeps
at `chiprun_out/benchmark/<cell>/job.log` on every run; a line is a PROCESS,
whatever roles it holds (the local launcher is the master too). A resident
cell: this process drove `Trainer` itself, so the fold is called over the
tracer's in-memory records, cut at the window's start. A program from before
the ledger existed gives None, and every metric that reads this is then left
out of the line.

In the job cell five parts add up to the time under any `start.*` span between
the launcher's start and the first task's completion line:

    start_process_s     start.launch, and start.spawn up to the worker's main()
    start_backend_s     start.connect + start.backend + start.trainer
    setup_state_s       start.state (+ ckpt.restore)
    setup_compile_s     compile
    start_first_task_s  start.first_task's own time, cut at the completion line

`start_named_pct` is that time's share of the whole stretch.
"""

from __future__ import annotations

import json
import os
import sys
import time

from benchmark import common

LEDGER_LINE = "start-up ledger: "
TASK_LINE = "training task "
STATE_SPANS = ("start.state", "ckpt.restore")
COUNTING_MISSES = ("compile", "start.state")


def ledgers_of_log(text: str) -> list:
    """Every process's ledger in a job's log, in the order they were
    printed."""
    out = []
    for line in text.splitlines():
        if LEDGER_LINE in line:
            try:
                out.append(json.loads(line.split(LEDGER_LINE, 1)[1]))
            except ValueError:
                continue        # a line cut by the job's end
    return out


def first_completion(text: str):
    """Wall-clock stamp of the first `training task` completion line."""
    for line in text.splitlines():
        if TASK_LINE in line and " step(s), " in line:
            return common.stamp(line)
    return None


def _seconds(ledger: dict, names) -> float:
    return sum(ledger["spans"][n]["s"] for n in names if n in ledger["spans"])


def _union(intervals) -> float:
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total, reach = total + (end - start), end
        elif end > reach:
            total, reach = total + (end - reach), end
    return total


def trainer_figures(ledger: dict) -> dict:
    """What every cell reads, from the ledger of the process that drives
    `Trainer`."""
    return {
        "setup_state_s": _seconds(ledger, STATE_SPANS),
        "setup_compile_s": _seconds(ledger, ("compile",)),
        "setup_cache_misses": sum(
            ledger["spans"][n].get("cache_misses", 0)
            for n in COUNTING_MISSES if n in ledger["spans"]),
    }


def job_figures(ledgers: list, done_at: float):
    """The job cell's seven figures from its processes' ledgers and the stamp
    of the first completion line; None unless one process launched and one
    trained."""
    launcher = next((l for l in ledgers if "start.launch" in l["spans"]), None)
    worker = next((l for l in ledgers if "start.process" in l["spans"]), None)
    if launcher is None or worker is None or done_at is None:
        return None

    def interval(ledger, name):
        span = ledger["spans"].get(name)
        return (span["ts"], span["ts"] + span["s"]) if span else None

    launch = interval(launcher, "start.launch")
    main_at = interval(worker, "start.process")[1]
    spawn = interval(launcher, "start.spawn")
    pieces = [launch]
    if spawn is not None and main_at > spawn[0]:
        pieces.append((spawn[0], main_at))
    out = trainer_figures(worker)
    out["start_process_s"] = sum(e - s for s, e in pieces)
    boot = [iv for iv in (interval(worker, n) for n in (
        "start.connect", "start.backend", "start.trainer")) if iv]
    out["start_backend_s"] = sum(e - s for s, e in boot)
    first_task = worker["spans"].get("start.first_task")
    out["start_first_task_s"] = 0.0
    if first_task is not None:
        task = interval(worker, "start.first_task")
        pieces.append((task[0], min(task[1], done_at)))
        # its own time: all of it but the children, which end inside the task
        out["start_first_task_s"] = max(
            0.0, first_task["self_s"] - max(0.0, task[1] - done_at))
    named = _union(pieces + boot)
    parts = (out["start_process_s"] + out["start_backend_s"]
             + out["setup_state_s"] + out["setup_compile_s"]
             + out["start_first_task_s"])
    # the five parts are a partition of the named time
    assert abs(parts - named) <= 0.01 * max(named, 1.0), (parts, named)
    out["start_named_pct"] = 100.0 * named / max(done_at - launch[0], 1e-9)
    return out


def window_opened_at(run: dict) -> float:
    """Wall-clock stamp of the resident window's start: `setup_s` counts from
    the `T0` of `benchmark/run.py`, on the monotonic clock."""
    t0 = getattr(sys.modules.get("__main__"), "T0", None)
    if t0 is None:
        return time.time()
    return time.time() - (time.monotonic() - (t0 + run["setup_s"]))


def figures(run: dict):
    """The run's start-up figures, read once and left on `run` for the
    metrics that ask; None where the program keeps no ledger."""
    if "start_spans" in run:
        return run["start_spans"]
    run["start_spans"] = None
    if run.get("job") is not None:
        path = os.path.join(common.OUT_DIR, run.get("workload") or "", "job.log")
        if os.path.exists(path):
            with open(path, errors="replace") as f:
                text = f.read()
            run["start_spans"] = job_figures(
                ledgers_of_log(text), first_completion(text))
        return run["start_spans"]
    from elasticdl_tpu.observability import profile, tracing

    fold = getattr(tracing, "startup_ledger", None)
    if fold is None:
        return None
    ledger = fold(list(tracing.get_tracer().records),
                  until=window_opened_at(run),
                  outside=profile.compile_outside())
    if ledger is not None:
        print(f"{LEDGER_LINE}{json.dumps(ledger)}", flush=True)
        run["start_spans"] = trainer_figures(ledger)
    return run["start_spans"]


def read(run: dict, name: str):
    f = figures(run)
    return None if f is None else f.get(name)


if __name__ == "__main__":
    with open(sys.argv[1], errors="replace") as f:
        text = f.read()
    ledgers = ledgers_of_log(text)
    print(json.dumps({"figures": job_figures(ledgers, first_completion(text)),
                      "ledgers": ledgers}, indent=1))
