"""What every part of the benchmark shares: where its files are, how a cell's
name resolves to them, the table of peaks and the log stamps.

Nothing here imports JAX: the job driver's process must stay off the chip.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from datetime import datetime

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(ROOT, "chiprun_out", "benchmark")
# run-time files (the .cbin shard, profiler traces, job logs): inside the
# checkout, listed in .gitignore, emptied per cell at the start of a run
WORK_DIR = os.path.join(ROOT, ".bench_work")


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py as a module — drivers, layer metrics,
    references and shape functions are all found by name, never listed."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve_cell(workload: str) -> dict:
    """BENCHMARK.json's entry for `workload`, with its configuration and
    traffic files read. A cell's name selects files and nothing else."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")

    def applies(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def model_name(config: dict) -> str:
    """`deepfm.xdeepfm.custom_model` -> `xdeepfm`: the name under which the
    configuration's reference and shape functions are kept."""
    return config["model_def"].split(".")[-2]


def model_params(config: dict) -> dict:
    out = {}
    for part in config["model_params"].split(";"):
        key, value = part.split("=", 1)
        out[key] = value
    return out


def format_model_params(params: dict) -> str:
    return ";".join(f"{k}={v}" for k, v in params.items())


def peaks(device_kind: str) -> dict:
    table = load_json("peaks.json")
    if device_kind not in table:
        raise RuntimeError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"({sorted(table)}); add its published peaks with their source")
    return table[device_kind]


_STAMP = re.compile(r"\[(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3})\]")


def stamp(line: str):
    """Seconds since the epoch of a log line's `[date time,ms]` stamp, or
    None for a line without one."""
    m = _STAMP.search(line)
    if not m:
        return None
    t = datetime.strptime(m.group(1), "%Y-%m-%d %H:%M:%S")
    return t.timestamp() + int(m.group(2)) / 1e3

