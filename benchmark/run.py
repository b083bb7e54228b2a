"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's name selects files and nothing else: `BENCHMARK.json` names the
cell's configuration (`benchmark/configs/`) and traffic mix
(`benchmark/traffic/`), the traffic file names its driver
(`benchmark/drivers/`), the configuration's `model_def` names its plain
reference and its shape functions (`benchmark/reference/`, `benchmark/flops/`)
and every metric has a reader of its own (`benchmark/end_to_end/`,
`benchmark/layer_metrics/`). A reader that finds nothing to read returns None
and its metric is left out of the line.

The last line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics, or with `--trace 1` its
per-layer metrics), `device`, and in a traced run `breakdown`. Earlier lines
say what the run did. With no TPU, or fewer chips than the cell asks for, the
run exits non-zero and prints no result. `--rehearse` (CPU, tiny sizes from
`benchmark/rehearse/tiny.json`) exists to find faults before a chip call; a
rehearsal's line names the platform it ran on and is never a measurement.
"""

from __future__ import annotations

import time

T0 = time.monotonic()       # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import common  # noqa: E402


def _apply_rehearsal(resolved: dict) -> None:
    """Shrink rows, batch and counts — never a width — to what a CPU runs in
    seconds. The overrides are data: benchmark/rehearse/tiny.json."""
    tiny = common.load_json("rehearse", "tiny.json")
    params = common.model_params(resolved["config"])
    params.update({k: str(v) for k, v in tiny["model_params"].items()})
    resolved["config"]["model_params"] = common.format_model_params(params)
    driver = resolved["traffic"]["driver"]
    resolved["traffic"].update(tiny["traffic"].get(driver, {}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    for needed in ("elasticdl_tpu", "model_zoo"):
        if not os.path.isdir(os.path.join(common.ROOT, needed)):
            print(f"{needed}/ is not in this checkout: there is no system to "
                  "measure", file=sys.stderr)
            return 2

    resolved = common.resolve_cell(args.workload)
    if args.rehearse:
        _apply_rehearsal(resolved)
    work_dir = os.path.join(common.WORK_DIR, args.workload)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    keep_dir = os.path.join(common.OUT_DIR, args.workload)

    check_failed = []

    def say(text):
        # every driver says a number that passed its limit as "CHECK FAILED:
        # <name> <value> > <limit>": kept for the result line, so that the
        # record of a run that read `correct: false` says which number it was
        if text.startswith("CHECK FAILED: "):
            check_failed.append(text[len("CHECK FAILED: "):])
        print(f"[{time.monotonic() - T0:7.1f}s] {text}", flush=True)

    def keep(path, name):
        """Copy an artefact worth a look (a trace, a job log) to where the
        chip tool brings files back from."""
        os.makedirs(keep_dir, exist_ok=True)
        shutil.copyfile(path, os.path.join(keep_dir, name))

    ctx = dict(resolved, t0=T0, seed=args.seed, seconds=args.seconds,
               trace=bool(args.trace), rehearse=args.rehearse,
               work_dir=work_dir, say=say, keep=keep)
    say(f"cell {args.workload}: configuration {resolved['config']['name']} "
        f"({resolved['config']['model_params']}), traffic "
        f"{resolved['traffic']['name']}, seed {args.seed}, "
        f"{'traced' if args.trace else 'untraced'}"
        + (", REHEARSAL at tiny size" if args.rehearse else ""))
    driver = common.load_module("drivers", resolved["traffic"]["driver"])
    try:
        run = driver.run(ctx)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    run["workload"] = args.workload

    kind, wanted = (("layer_metrics", resolved["per_layer"]) if args.trace
                    else ("end_to_end", resolved["end_to_end"]))
    metrics = {}
    for entry in wanted:
        value = common.load_module(kind, entry["name"]).read(run)
        if value is None:
            say(f"metric {entry['name']}: nothing to read in this run, left out")
            continue
        metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}

    device = dict(run["device"])
    line = {
        "correct": bool(run["correct"]),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": metrics,
        "device": device,
    }
    traced = run.get("trace")
    if args.trace and traced:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        line["breakdown"] = {"device_ops": traced["device_ops"],
                             "idle_gaps": traced["idle_gaps"]}
    if args.rehearse:
        line["rehearsal"] = True
    line["check_failed"] = check_failed     # last: what the check held against
    print(json.dumps(line), flush=True)     # a limit and found over it
    for failure in check_failed:
        print(f"CHECK FAILED: {failure}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
