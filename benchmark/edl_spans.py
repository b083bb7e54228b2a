"""From the worker loop's own spans in a profiler trace to the device's idle
time under each of them.

The program writes its spans into the profiler's trace as `edl.*` annotations
(`elasticdl_tpu/observability/profile.py::annotation`), on the clock the
device's events are on. On the thread that runs the task loop they nest:

    edl.task_turn { edl.lease  edl.task { edl.data_wait  edl.h2d
        edl.compute { edl.h2d  edl.compute.dispatch  edl.compute.readback }
        edl.handoff }  edl.report }

Every idle gap of device 0 inside the reduction's window is split among them
by the INNERMOST span that covers each instant (`trace_reduce.attribute_gaps`
gives a whole gap to the widest cover, which among nested spans is always the
outermost), and each span's share goes to one of five buckets. Spans on other
threads (`edl.input.make_batch` on the parse pool) never take a gap: the task
loop's thread is the one that keeps the device waiting. A dispatch is one
`edl.compute` span that begins inside the window.

`trace_reduce.reduce_file` and `trace_reduce.host_annotations` are used as
they stand; the job driver keeps the trace it reduced at
`chiprun_out/benchmark/<cell>/trace.xplane.pb`, and that file is what
`figures(run)` opens. A trace without `edl.compute` spans (a program from
before they existed, a resident cell) gives None, and every metric that reads
this is then left out of the line.
"""

from __future__ import annotations

import os
from bisect import bisect_right
from types import SimpleNamespace

from benchmark import common, trace_reduce

PREFIX = "edl."
DISPATCH = "edl.compute"
# counted, never used to attribute a gap, on whatever thread it runs
NOT_ATTRIBUTING = ("edl.input.make_batch",)
BUCKETS = ("input", "h2d", "step", "turn", "loop")
# a span's bucket; a span that is not listed (a bridged `tracing.span` such
# as edl.ckpt.save) has the bucket of the nearest span around it, and
# `loop` with none around it
BUCKET_OF = {
    "edl.data_wait": "input",
    "edl.h2d": "h2d",
    "edl.compute": "step",
    "edl.compute.dispatch": "step",
    "edl.compute.readback": "step",
    "edl.task_turn": "turn",
    "edl.lease": "turn",
    "edl.lease.wait": "turn",
    "edl.report": "turn",
    "edl.task": "loop",
    "edl.handoff": "loop",
    "edl.compile": "loop",
}


def task_loop_spans(profile) -> list:
    """(start_ns, end_ns, name) of the `edl.*` annotations on the host line
    that holds the most `edl.compute` spans: the thread of the task loop (a
    session that opens and closes inside task turns, as the worker's does,
    records the dispatches between and not the turns it cut).
    `host_annotations` reads a whole profile and forgets the line, so it is
    given one line at a time."""
    best, best_dispatches = [], 0
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            one_line = SimpleNamespace(planes=[SimpleNamespace(
                name=plane.name, lines=[line])])
            spans = trace_reduce.host_annotations(one_line, prefix=PREFIX)
            dispatches = sum(1 for s in spans if s[2] == DISPATCH)
            if dispatches > best_dispatches:
                best, best_dispatches = spans, dispatches
    return best


def innermost_segments(spans) -> list:
    """Nested spans of one thread -> disjoint (start_ns, end_ns, path), path
    being the names of the spans that cover the segment, outermost first. A
    span that outlasts the one around it (two clock readings a tick apart) is
    cut to it."""
    out = []
    stack = []          # [end, name]
    cursor = 0.0

    def emit(until):
        nonlocal cursor
        if stack and until > cursor:
            out.append((cursor, until, tuple(name for _, name in stack)))
        cursor = max(cursor, until)

    for start, end, name in sorted(
            (s for s in spans if s[2] not in NOT_ATTRIBUTING),
            key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= start:
            emit(stack[-1][0])
            stack.pop()
        emit(start)
        if stack:
            end = min(end, stack[-1][0])
        stack.append([end, name])
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def bucket_of(path) -> str:
    for name in reversed(path):
        if name in BUCKET_OF:
            return BUCKET_OF[name]
    return "loop"


def split_gaps(gaps_ns, segments) -> dict:
    """ns of the gaps under each bucket, under each innermost span's name,
    and under no span at all (`None`)."""
    starts = [s for s, _, _ in segments]
    by_bucket = dict.fromkeys(BUCKETS, 0.0)
    by_span = {}
    for g0, g1 in gaps_ns:
        i = max(0, bisect_right(starts, g0) - 1)
        while i < len(segments) and segments[i][0] < g1:
            s, e, path = segments[i]
            cover = min(g1, e) - max(g0, s)
            if cover > 0:
                by_bucket[bucket_of(path)] += cover
                by_span[path[-1]] = by_span.get(path[-1], 0.0) + cover
            i += 1
    idle = sum(g1 - g0 for g0, g1 in gaps_ns)
    named = sum(by_span.values())
    # the five buckets are a partition of the named idle time
    assert abs(sum(by_bucket.values()) - named) <= 1e-6 * max(named, 1.0)
    by_span[None] = idle - named
    return {"idle_ns": idle, "named_ns": named, "by_bucket": by_bucket,
            "by_span": by_span}


def figures_of(path: str):
    """The whole reading of one trace file, or None where it has no device
    window or no task-loop spans."""
    from jax.profiler import ProfileData

    reduced = trace_reduce.reduce_file(path)
    if not reduced["devices"]:
        return None
    device = reduced["devices"][min(reduced["devices"])]
    spans = task_loop_spans(ProfileData.from_file(path))
    if not spans:
        return None
    w0, w1 = device["window_ns"]
    out = split_gaps(device["gaps_ns"], innermost_segments(spans))
    out["dispatches"] = sum(
        1 for s, _, name in spans if name == DISPATCH and w0 <= s < w1)
    out["window_ns"] = (w0, w1)
    out["spans_in_window"] = {}
    for s, e, name in spans:
        if e > w0 and s < w1:
            out["spans_in_window"][name] = out["spans_in_window"].get(name, 0) + 1
    if not out["dispatches"]:
        return None
    return out


def figures(run):
    """`figures_of` the trace the driver kept for this run; None for a run
    without a trace. Read once and left on `run` for the six metrics that
    ask."""
    if "edl_spans" not in run:
        path = os.path.join(
            common.OUT_DIR, run.get("workload") or "", "trace.xplane.pb")
        traced = bool(run.get("trace")) and os.path.exists(path)
        run["edl_spans"] = figures_of(path) if traced else None
    return run["edl_spans"]


def gap_ms(run, bucket: str):
    """ms of device idle per dispatch under `bucket`."""
    f = figures(run)
    if f is None:
        return None
    return f["by_bucket"][bucket] / 1e6 / f["dispatches"]


if __name__ == "__main__":
    import json
    import sys

    f = figures_of(sys.argv[1])
    if f is None:
        raise SystemExit("no device window or no edl.compute span in this trace")
    per = 1e6 * f["dispatches"]
    print(json.dumps({
        "window_ms": (f["window_ns"][1] - f["window_ns"][0]) / 1e6,
        "dispatches": f["dispatches"],
        "idle_ms_per_dispatch": f["idle_ns"] / per,
        "idle_named_pct": 100.0 * f["named_ns"] / max(f["idle_ns"], 1.0),
        "bucket_ms_per_dispatch": {k: v / per for k, v in f["by_bucket"].items()},
        "span_ms_per_dispatch": {str(k): v / per for k, v in sorted(
            f["by_span"].items(), key=lambda kv: -kv[1])},
        "spans_in_window": f["spans_in_window"],
    }, indent=1))
