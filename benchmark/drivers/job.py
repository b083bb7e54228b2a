"""Driver `job`: the whole job, as a user starts it.

This process stays off JAX (a process that has touched JAX holds the chip).
It writes `criteo-skew` records to one `.cbin` shard with the program's own
`parsing.criteo_bin_encode`, starts `python -m elasticdl_tpu.client.main
train` — launcher, master, `ProcessManager`, worker, `.cbin` reader,
`Trainer` — and reads what master and worker log (`chip_smoke.py`'s way of
reading a job, copied). Warm-up ends at the completion line of the
`warmup_tasks`-th task; the window is the next `--seconds`; then the launcher
is interrupted, so that the master prints its own accounting, and the whole
process group is waited for. A process that has to be killed is a failed run.

`--trace 1` adds the worker's `--profile_dir` window just past warm-up; the
trace is reduced after the job has gone, and the log-derived per-layer
figures are then taken from the tasks after the profiler stopped.
"""

from __future__ import annotations

import ast
import json
import os
import re
import signal
import statistics
import subprocess
import sys
import time
import urllib.request

from benchmark import common, criteo_skew

TASK_LINE = re.compile(
    r"training task (\d+): (\d+) step\(s\), ([\d.]+) ms/step, mean loss (\S+)")
TROUBLE = re.compile(
    r"task \d+ failed|lease expired|failed permanently|requeued|stale/unknown "
    r"task report|rejecting report|Traceback \(most recent call last\)")


def read_log(text: str) -> dict:
    """What the job's log says, by `chip_smoke.py`'s reading: the
    worker's own statement of its devices, one entry per task completion
    line, the master's closing accounting, and every line that means a task
    did not simply finish once."""
    out = {"devices": None, "tasks": [], "counts": None,
           "counts_at": None, "trouble": [], "metrics_url": None,
           "profile_stopped": None}
    at = None
    for line in text.splitlines():
        at = common.stamp(line) or at       # a traceback's lines have none
        if "training devices: {" in line and out["devices"] is None:
            out["devices"] = json.loads(line.split("training devices: ", 1)[1])
        elif "metrics endpoint serving on " in line and "worker-0" in line:
            out["metrics_url"] = re.search(r"(http://\S+/metrics)", line).group(1)
        elif "profiler trace stopped" in line:
            out["profile_stopped"] = at
        elif "job finished: " in line:
            m = re.search(r"job finished: (\{.*?\}) mean_loss=", line)
            out["counts"], out["counts_at"] = ast.literal_eval(m.group(1)), at
        m = TASK_LINE.search(line)
        if m:
            out["tasks"].append({
                "id": int(m.group(1)), "steps": int(m.group(2)),
                "ms_per_step": float(m.group(3)), "loss": float(m.group(4)),
                "at": at})
        if TROUBLE.search(line):
            out["trouble"].append((at, line.strip()[:300]))
    return out


def window_figures(log: dict, traffic: dict, seconds: float,
                   after: float = None) -> dict:
    """The window's figures from the task completion lines. The window opens
    at the stamp of the `warmup_tasks`-th completion and lasts `seconds`.
    Every task completed inside it is one reading of the rate: its records
    over the time since the completion before it (every interval between two
    stamps is one whole task: lease, read, parse, steps, report). The window's
    rate is the median reading, so that one task a neighbour on the shared
    host stalls costs one reading and not its share of the wall. `after`:
    only tasks completed later than this stamp enter step_ms and
    host_wait_pct."""
    tasks, warm = log["tasks"], int(traffic["warmup_tasks"])
    if len(tasks) <= warm:
        return {}
    opened = tasks[warm - 1]["at"]
    inside = [t for t in tasks[warm:] if t["at"] <= opened + seconds]
    if not inside:
        return {}
    batch = int(traffic["minibatch_size"])
    wall = inside[-1]["at"] - opened
    samples = sum(t["steps"] for t in inside) * batch
    # per-layer: the stretch after `after`, measured between stamps
    prev = opened
    stretch_wall, stretch_step = 0.0, 0.0
    step_ms, rates = [], []
    for t in inside:
        rates.append(t["steps"] * batch / max(t["at"] - prev, 1e-3))  # stamps are in ms
        if after is None or prev >= after:
            stretch_wall += t["at"] - prev
            stretch_step += t["steps"] * t["ms_per_step"] / 1e3
            step_ms.append(t["ms_per_step"])
        prev = t["at"]
    out = {
        "opened": opened, "wall_s": wall, "samples": samples,
        "steps": sum(t["steps"] for t in inside), "tasks": len(inside),
        "samples_per_s": statistics.median(rates),
        "finite": all(abs(t["loss"]) < float("inf") for t in inside),
    }
    if step_ms and stretch_wall > 0:
        out["step_ms"] = statistics.median(step_ms)
        out["host_wait_pct"] = 100.0 * (1.0 - stretch_step / stretch_wall)
    return out


def accounting_ok(log: dict, stopped_at: float) -> list:
    """The master's own accounting, as far as a run that is stopped can show
    it: every task the worker completed has its own id (none leased twice),
    the master's closing count of finished tasks is the worker's count of
    completion lines up to that moment — one report may be in flight — none
    failed, and no line speaks of a requeue, an expired lease or a rejected
    report before the stop (the stop itself preempts the task in flight,
    which the master then requeues)."""
    problems = [line for at, line in log["trouble"]
                if at is None or at < stopped_at]
    ids = [t["id"] for t in log["tasks"]]
    reported = sum(1 for t in log["tasks"]
                   if log["counts_at"] is None or t["at"] <= log["counts_at"])
    if len(set(ids)) != len(ids):
        problems.append(f"a task completed twice: {sorted(ids)}")
    counts = log["counts"]
    if counts is None:
        problems.append("the master printed no `job finished` accounting")
    else:
        if counts.get("failed_permanently"):
            problems.append(f"master counts failed tasks: {counts}")
        if abs(counts["finished_training"] - reported) > 1:
            problems.append(
                f"master finished {counts['finished_training']} tasks, the "
                f"worker had reported {reported}")
    return problems


def write_shard(path: str, records: dict) -> None:
    from elasticdl_tpu.data import parsing

    with open(path + ".tmp", "wb") as f:
        f.write(parsing.criteo_bin_encode(
            records["labels"], records["dense"], records["cat"]))
    os.replace(path + ".tmp", path)


def input_host_rate(data_dir: str, batch: int) -> float:
    """Samples per second of the program's input path alone — reader, blob
    parser, `TaskDataService.batches` with its look-ahead — over the cell's
    shard, no device."""
    from elasticdl_tpu.data import parsing
    from elasticdl_tpu.data.reader import create_data_reader
    from elasticdl_tpu.worker.task_data_service import TaskDataService

    reader = create_data_reader(data_dir, "")
    service = TaskDataService(
        reader, parsing.criteo_bin_batch_parser(
            criteo_skew.NUM_DENSE, criteo_skew.NUM_CAT), batch)
    count = 0
    t0 = time.perf_counter()
    try:
        for shard, start, end in reader.create_shards():
            for b in service.batches(shard, start, end):
                count += int(b["mask"].sum())
    finally:
        service.close()
    return count / (time.perf_counter() - t0)


def _group_alive(pgid: int) -> bool:
    try:
        os.killpg(pgid, 0)
        return True
    except ProcessLookupError:
        return False


def stop_job(proc, grace_s: float, say) -> bool:
    """Interrupt the launcher (its `finally` shuts the master down, which
    prints the accounting, and stops the workers), then wait for the whole
    group. True when nothing had to be killed."""
    pgid = proc.pid

    def wait_group(seconds):
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline:
            if proc.poll() is not None and not _group_alive(pgid):
                return True
            time.sleep(0.1)
        return proc.poll() is not None and not _group_alive(pgid)

    if proc.poll() is None:
        proc.send_signal(signal.SIGINT)
    if wait_group(grace_s):
        return True
    say(f"job still alive {grace_s:.0f} s after the interrupt: terminating its group")
    for sig, seconds in ((signal.SIGTERM, 15.0), (signal.SIGKILL, 10.0)):
        try:
            os.killpg(pgid, sig)
        except ProcessLookupError:
            pass
        if wait_group(seconds):
            break
    proc.wait()
    return False


def run(ctx) -> dict:
    config, traffic = ctx["config"], ctx["traffic"]
    chips, seed, say = int(ctx["cell"]["chips"]), ctx["seed"], ctx["say"]
    platform = "cpu" if ctx["rehearse"] else "tpu"
    wall_t0 = time.time() - (time.monotonic() - ctx["t0"])
    batch = int(traffic["minibatch_size"])
    steps_per_task = int(traffic["records_per_task"]) // batch
    warm_steps = int(traffic["warmup_tasks"]) * steps_per_task

    # ---- the shard, from the seed ---------------------------------------- #
    t = time.monotonic()
    data_dir = os.path.join(ctx["work_dir"], "data")
    os.makedirs(data_dir)
    cardinalities = common.load_json(
        "cardinalities", config["cardinalities"] + ".json")["fields"]
    write_shard(os.path.join(data_dir, "criteo-00000.cbin"),
                criteo_skew.from_traffic(
                    seed, int(traffic["records"]), cardinalities, traffic))
    say(f"wrote {traffic['records']} records to a .cbin shard in "
        f"{time.monotonic() - t:.1f} s")
    job = {}
    if ctx["trace"]:
        job["input_host_samples_per_s"] = input_host_rate(data_dir, batch)
        say(f"input path alone: {job['input_host_samples_per_s']:.0f} samples/s")

    # ---- the job, as a user starts it ------------------------------------ #
    argv = [
        sys.executable, "-m", "elasticdl_tpu.client.main", "train",
        "--job_name", "bench-" + ctx["cell"]["name"].replace(".", "-"),
        "--model_zoo", os.path.join(common.ROOT, "model_zoo"),
        "--model_def", config["model_def"],
        "--model_params", config["model_params"],
        "--minibatch_size", str(batch),
        "--steps_per_dispatch", str(traffic["steps_per_dispatch"]),
        "--training_data", data_dir,
        "--records_per_task", str(traffic["records_per_task"]),
        "--num_epochs", str(traffic["num_epochs"]),
        "--shuffle_seed", str(seed),
        "--master_addr", "localhost:0",
    ]
    if config.get("mesh_shape"):
        argv += ["--mesh_shape", config["mesh_shape"]]
    trace_dir = os.path.join(ctx["work_dir"], "trace")
    if ctx["trace"]:
        argv += ["--profile_dir", trace_dir,
                 "--profile_start_step", str(warm_steps + steps_per_task),
                 "--profile_steps", str(traffic["profile_steps"])]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platform
    env["PYTHONPATH"] = common.ROOT + os.pathsep + env.get("PYTHONPATH", "")
    log_path = os.path.join(ctx["work_dir"], "job.log")
    launched = time.time()
    with open(log_path, "wb") as log_file:
        proc = subprocess.Popen(
            argv, cwd=common.ROOT, env=env, stdout=log_file,
            stderr=subprocess.STDOUT, start_new_session=True)
    clean = False
    peak_bytes = 0
    try:
        deadline = time.monotonic() + 1100.0
        closes = None
        while True:
            time.sleep(0.2)
            with open(log_path, errors="replace") as f:
                log = read_log(f.read())
            if log["devices"] and (log["devices"]["platform"] != platform
                                   or log["devices"]["device_count"] != chips):
                raise SystemExit(
                    f"the worker trains on {log['devices']}, the cell needs "
                    f"{chips} x {platform}")
            if closes is None and len(log["tasks"]) >= int(traffic["warmup_tasks"]):
                closes = log["tasks"][int(traffic["warmup_tasks"]) - 1]["at"] \
                    + ctx["seconds"]
                say(f"warm-up over after {len(log['tasks'])} task(s); the "
                    f"window closes in {closes - time.time():.1f} s")
            if closes is not None and time.time() >= closes + 0.3:
                break
            if proc.poll() is not None:
                raise SystemExit(
                    f"the job exited with {proc.returncode} before the window "
                    f"closed; log: {log_path}")
            if time.monotonic() > deadline:
                raise SystemExit("the job never reached the window's end")
        if log["metrics_url"]:
            try:
                with urllib.request.urlopen(log["metrics_url"], timeout=5) as r:
                    m = re.search(r"^edl_mem_device_peak_mb(?:\{[^}]*\})? (\S+)",
                                  r.read().decode(), re.M)
                    peak_bytes = int(float(m.group(1)) * 2 ** 20) if m else 0
            except OSError as e:
                say(f"the worker's /metrics did not answer: {e}")
    finally:
        stopped_at = time.time()
        clean = stop_job(proc, float(traffic["stop_grace_s"]), say)
        ctx["keep"](log_path, "job.log")
    with open(log_path, errors="replace") as f:
        log = read_log(f.read())

    figures = window_figures(
        log, traffic, ctx["seconds"],
        after=log["profile_stopped"] if ctx["trace"] else None)
    if not figures:
        raise SystemExit("no task completed inside the window")
    problems = accounting_ok(log, stopped_at)
    if not clean:
        problems.append("a process of the job had to be terminated")
    if not figures["finite"]:
        problems.append("a task's mean loss is not finite")
    for p in problems:
        say(f"CHECK FAILED: {p}")
    job["first_step_s"] = log["tasks"][0]["at"] - launched
    job["host_wait_pct"] = figures.get("host_wait_pct")
    say(f"window: {figures['tasks']} tasks, {figures['steps']} steps in "
        f"{figures['wall_s']:.3f} s ({figures['samples'] / figures['wall_s'] / chips:.1f} "
        f"samples/s/chip over the whole wall, {figures['samples_per_s'] / chips:.1f} "
        f"by the median task); master: {log['counts']}; "
        f"launch to first task {job['first_step_s']:.1f} s")

    traced = None
    if ctx["trace"]:
        from benchmark import trace_reduce

        try:
            path = trace_reduce.find_xplane(trace_dir)
        except FileNotFoundError as e:
            say(f"no trace came back: {e}")
        else:
            ctx["keep"](path, "trace.xplane.pb")
            traced = trace_reduce.summary(trace_reduce.reduce_file(path)) or None
            if traced:
                traced["steps"] = None      # the job's trace is cut by time
                say(f"trace reduced: busy {traced['busy_s']:.3f} s of "
                    f"{traced['window_s']:.3f} s")

    return {
        "correct": not problems,
        "attempted": figures["tasks"],
        "failed": 0 if not problems else figures["tasks"],
        "setup_s": figures["opened"] - wall_t0,
        "window": {"wall_s": figures["wall_s"], "steps": figures["steps"],
                   "samples": figures["samples"], "chips": chips,
                   "batch": batch, "readings": figures["tasks"],
                   "samples_per_s": figures["samples_per_s"],
                   "step_ms": figures.get("step_ms")},
        "device": {"platform": log["devices"]["platform"],
                   "kind": log["devices"]["device_kind"],
                   "count": log["devices"]["device_count"],
                   "memory_peak_bytes": peak_bytes},
        "trace": traced,
        "job": job,
    }
