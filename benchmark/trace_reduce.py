"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer metrics
read: device busy and idle time, time per operation, Mosaic custom calls (in
all and by kernel), collectives and their exposed part, idle gaps and what the
host was doing in them. Reads the file through `jax.profiler.ProfileData` and
nothing else.

What a v5e trace looks like (looked at by hand, PR 22): one plane per chip,
`/device:TPU:<n>`, with the lines `Steps`, `XLA Modules` (one event per run of
an executable), `XLA Ops` and `Async XLA Ops` (start-to-done spans of
asynchronous copies and collectives); host threads are lines of the plane
`/host:CPU`. Every event carries a start and a duration in nanoseconds on one
clock, and is named by its HLO instruction's whole text; no event carries the
JAX name stack. On `XLA Ops` a `while` (the scan over a dispatch's steps) or a
`conditional` is itself an event that spans its body's events, so busy time
is the union of the LEAF events — those that contain no other — and an
operation's own time is its duration less its children's.

Nothing here knows a model or a cell.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"        # start-to-done spans of asynchronous ops
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute")
# events that only group others: never an operation's own work
CONTAINERS = ("while", "conditional", "call")
# `%all_to_all.10 = f32[...]{...} all-to-all(...)`: the instruction's name, then
# its opcode
_INSTRUCTION = re.compile(r"%?([\w.\-]+) = .*?\s([\w\-]+)\(")


def find_xplane(trace_dir: str) -> str:
    """The newest `.xplane.pb` under a `jax.profiler.start_trace` directory."""
    paths = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def op_kind(name: str) -> str:
    """`%fusion.12 = ...` / `fusion.12` -> `fusion`; the HLO opcode-like stem
    an event's name starts with."""
    stem = name.lstrip("%").split(" ", 1)[0].split("=", 1)[0]
    return re.sub(r"[.\d]+$", "", stem) or stem


def opcode(name: str) -> str:
    """`%all_to_all.10 = f32[8] all-to-all(...)` -> `all-to-all`: the HLO
    opcode of an event named by its instruction's whole text. JAX names an
    instruction for the primitive that made it (`all_to_all`, `pmax`), so the
    name's stem is not the opcode; an event that carries the name alone has
    nothing else to give (`op_kind`)."""
    m = _INSTRUCTION.match(name)
    return m.group(2) if m else op_kind(name)


def is_collective(name: str) -> bool:
    kind = opcode(name)
    return any(kind == c or kind.startswith(c + "-") for c in COLLECTIVES)


def is_mosaic(name: str) -> bool:
    """A Mosaic (Pallas) kernel: an event's name is its HLO instruction's
    text, and XLA calls such a kernel through `tpu_custom_call`."""
    return "tpu_custom_call" in name


def short_name(name: str) -> str:
    """`%place_sorted_grads.4 = f32[...] custom-call(...)` ->
    `place_sorted_grads.4 (custom-call)`: what a breakdown can carry."""
    m = _INSTRUCTION.match(name)
    return f"{m.group(1)} ({m.group(2)})" if m else name[:80]


def seconds_by_kernel(per_op_s: dict) -> dict:
    """Seconds of the Mosaic kernels of one device's `per_op_s`, by kernel:
    `%place_sorted_grads.4 = ... custom-call(...)` counts under
    `place_sorted_grads`, the `name=` its `pallas_call` was given, whatever
    number the compiler put behind it."""
    out = defaultdict(float)
    for text, seconds in per_op_s.items():
        if is_mosaic(text):
            name = text.lstrip("%").split(" ", 1)[0]
            out[re.sub(r"\.\d+$", "", name)] += seconds
    return dict(out)


def _events(line):
    out = []
    for ev in line.events:
        start = float(ev.start_ns)
        out.append((start, start + float(ev.duration_ns), ev.name, ev))
    out.sort(key=lambda e: (e[0], -e[1]))
    return out


def _self_times(events):
    """[(start, end, name, self_ns, is_leaf, raw)] by time containment."""
    out = []
    stack = []          # indices into out
    for start, end, name, raw in events:
        while stack and out[stack[-1]][1] <= start:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= min(end, parent[1]) - start
            parent[4] = False
        out.append([start, end, name, end - start, True, raw])
        stack.append(len(out) - 1)
    return out


def _union(intervals):
    total, cur_s, cur_e = 0.0, None, None
    merged = []
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                merged.append((cur_s, cur_e))
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        merged.append((cur_s, cur_e))
        total += cur_e - cur_s
    return total, merged


def _overlap(a, intervals):
    s0, e0 = a
    return sum(max(0.0, min(e0, e) - max(s0, s)) for s, e in intervals)


def _stats(raw) -> dict:
    try:
        return dict(raw.stats)
    except Exception:       # an event without readable stats has none
        return {}


def reduce_plane(plane, window=None) -> dict:
    """One device's plane -> seconds. `window` (start_ns, end_ns) clips the
    reading; default is the span of the plane's operation events."""
    ops_line = next((ln for ln in plane.lines if ln.name == OPS_LINE), None)
    if ops_line is None:
        return {}
    events = _events(ops_line)
    if window is not None:
        events = [e for e in events if e[1] > window[0] and e[0] < window[1]]
    if not events:
        return {}
    timed = _self_times(events)
    w0 = window[0] if window else min(e[0] for e in events)
    w1 = window[1] if window else max(e[1] for e in events)
    leaves = [(max(s, w0), min(e, w1)) for s, e, _, _, leaf, _ in timed if leaf]
    busy_ns, merged = _union(leaves)
    per_op = defaultdict(float)
    mosaic_ns, mosaic_calls = 0.0, 0
    coll, other_leaves = [], []
    for s, e, name, self_ns, leaf, raw in timed:
        kind = op_kind(name)
        if kind in CONTAINERS and not leaf:
            continue
        per_op[name] += self_ns
        if is_collective(name):
            coll.append((s, e))
        elif leaf:
            other_leaves.append((s, e))
            if is_mosaic(name):
                mosaic_ns += e - s
                mosaic_calls += 1
    async_line = next((ln for ln in plane.lines if ln.name == ASYNC_LINE), None)
    if async_line is not None:
        # an asynchronous collective is a short -start and a -done on the
        # operations' line, and its time in flight is a span on this one
        coll += [(max(s, w0), min(e, w1)) for s, e, name, _ in _events(async_line)
                 if is_collective(name) and e > w0 and s < w1]
    coll_ns, coll_merged = _union(coll)
    _, other_merged = _union(other_leaves)
    exposed_ns = sum((e - s) - _overlap((s, e), other_merged)
                     for s, e in coll_merged)
    gaps = [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)]
    if merged and merged[0][0] > w0:
        gaps.append((w0, merged[0][0]))
    if merged and merged[-1][1] < w1:
        gaps.append((merged[-1][1], w1))
    modules = next((ln for ln in plane.lines if ln.name == MODULES_LINE), None)
    module_runs = [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns), ev.name)
                   for ev in modules.events] if modules is not None else []
    return {
        "window_ns": (w0, w1),
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "per_op_s": {k: v / 1e9 for k, v in per_op.items()},
        "mosaic_s": mosaic_ns / 1e9,
        "mosaic_calls": mosaic_calls,
        "collective_s": coll_ns / 1e9,
        "collective_exposed_s": exposed_ns / 1e9,
        "gaps_ns": sorted(gaps, key=lambda g: g[0] - g[1]),
        "module_runs": module_runs,
    }


def host_annotations(profile, prefix="bench.") -> list:
    """(start_ns, end_ns, name) of the benchmark's own TraceAnnotations on
    the host planes."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    s = float(ev.start_ns)
                    out.append((s, s + float(ev.duration_ns), ev.name))
    return out


def attribute_gaps(gaps_ns, annotations, top=10) -> list:
    """Longest idle gaps summed by what the host was doing in them: each gap
    goes to the annotation that covers most of it, or `unattributed`."""
    by_name = defaultdict(float)
    for s, e in gaps_ns:
        best, best_cover = "unattributed", 0.0
        for a_s, a_e, name in annotations:
            cover = max(0.0, min(e, a_e) - max(s, a_s))
            if cover > best_cover:
                best, best_cover = name, cover
        by_name[best] += (e - s) / 1e9
    return sorted(([k, v] for k, v in by_name.items()), key=lambda kv: -kv[1])[:top]


def program_spans(profile) -> list:
    """What the host was doing where the benchmark annotates nothing (a job
    runs the program's own loop): the program's `edl.*` spans on the task
    loop's thread, cut into disjoint pieces in time order, each with the path
    of spans over it, outermost first. `benchmark/edl_spans.py` finds the
    thread and cuts the pieces, as it does for the `gap_*` metrics."""
    from benchmark import edl_spans     # it imports this module

    return edl_spans.innermost_segments(edl_spans.task_loop_spans(profile))


def split_gaps(gaps_ns, pieces, top=10) -> list:
    """Idle seconds under each INNERMOST span of `program_spans`, by name;
    what lies under none is `unattributed`. A gap is SPLIT among its pieces
    (`edl_spans.split_gaps`, the `gap_*` metrics' own split), not given whole
    to the widest cover as `attribute_gaps` does: between two dispatches of a
    job the device idles ONCE, under the read-back's tail, the turn, the wait
    for a batch, its transfer and the dispatch, and the widest of them — or
    `edl.task_turn`, which covers a whole turn — would take it all."""
    from benchmark import edl_spans

    by_span = edl_spans.split_gaps(gaps_ns, pieces)["by_span"]
    rows = [["unattributed" if name is None else name, ns / 1e9]
            for name, ns in by_span.items() if ns > 0]
    return sorted(rows, key=lambda kv: -kv[1])[:top]


def reduce_file(path: str) -> dict:
    """The whole reduction of one trace file. The window is the span of the
    benchmark's annotations where there are any (resident cells), else the
    span of device 0's module runs less the first and the last (job cell:
    whole dispatches of a steady stretch), else every operation event. Where
    the benchmark annotated nothing, `program_spans` name the idle gaps of the
    breakdown, and nothing else: the window and every second stay as they are."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    planes = sorted(
        ((int(DEVICE_PLANE.match(p.name).group(1)), p)
         for p in profile.planes if DEVICE_PLANE.match(p.name)),
        key=lambda ip: ip[0])
    if not planes:
        return {"devices": {}, "annotations": [], "program_spans": []}
    annotations = host_annotations(profile)
    window = None
    if annotations:
        window = (min(a[0] for a in annotations), max(a[1] for a in annotations))
    devices = {}
    for index, plane in planes:
        red = reduce_plane(plane, window)
        if red:
            devices[index] = red
    if window is None and devices:
        runs = sorted(next(iter(devices.values()))["module_runs"])
        if len(runs) >= 3:
            window = (runs[1][0], runs[-1][0])
            devices = {i: reduce_plane(p, window) for i, p in planes}
            devices = {i: r for i, r in devices.items() if r}
    return {"devices": devices, "annotations": annotations,
            "program_spans": [] if annotations else program_spans(profile)}


def summary(reduced: dict) -> dict:
    """Averages over the chips used, and device 0's breakdown."""
    devices = reduced["devices"]
    if not devices:
        return {}
    n = len(devices)
    first = devices[min(devices)]
    by_short = defaultdict(float)
    for name, seconds in first["per_op_s"].items():
        by_short[short_name(name)] += seconds
    top_ops = sorted(by_short.items(), key=lambda kv: -kv[1])[:10]
    if reduced.get("program_spans"):
        idle_gaps = split_gaps(first["gaps_ns"], reduced["program_spans"])
    else:
        idle_gaps = attribute_gaps(first["gaps_ns"], reduced["annotations"])
    return {
        "chips_traced": n,
        "window_s": sum(d["window_s"] for d in devices.values()) / n,
        "busy_s": sum(d["busy_s"] for d in devices.values()) / n,
        "mosaic_s": first["mosaic_s"],
        "mosaic_calls": first["mosaic_calls"],
        "mosaic_kernel_s": seconds_by_kernel(first["per_op_s"]),
        "collective_s": first["collective_s"],
        "collective_exposed_s": first["collective_exposed_s"],
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": idle_gaps,
    }


def describe(path: str, limit: int = 40) -> str:
    """What a trace holds, for a reader who has to write a reduction: planes,
    lines, event counts and the commonest event names with their stats."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    out = []
    for plane in profile.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  line {line.name!r}: {len(events)} events")
            if not (plane.name.startswith("/device:") or "bench" in
                    " ".join(e.name for e in events[:2000])):
                continue
            by_name = defaultdict(lambda: [0, 0.0])
            for ev in events:
                by_name[ev.name][0] += 1
                by_name[ev.name][1] += ev.duration_ns
            ranked = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:limit]
            sample = {ev.name: ev for ev in events}
            for name, (count, ns) in ranked:
                stats = {k: str(v)[:80] for k, v in list(_stats(sample[name]).items())[:8]}
                out.append(f"    {ns / 1e6:10.3f} ms  x{count:<6d} {name[:120]}  {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1]))
