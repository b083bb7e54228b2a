"""layer: worker loop. Device 0's idle time per dispatch, in the traced window,
under `edl.task`'s own time (the loop's Python between spans: mask sums, step
statistics, the recorders themselves), `edl.handoff` and `edl.compile`.
Innermost span wins; the five `gap_*` add up to the named idle time
(benchmark/edl_spans.py)."""

from benchmark import edl_spans


def read(run):
    return edl_spans.gap_ms(run, "loop")
