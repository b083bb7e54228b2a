"""layer: launcher and master. Device 0's idle time per dispatch, in the traced
window, under `edl.lease` + `edl.report` + `edl.task_turn`'s own time: the two
RPCs of a task turn, the checkpoint decision, the task's log line and
counters. Innermost span wins; the five `gap_*` add up to the named idle time
(benchmark/edl_spans.py)."""

from benchmark import edl_spans


def read(run):
    return edl_spans.gap_ms(run, "turn")
