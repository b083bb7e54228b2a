"""layer: worker loop. Device 0's idle time per dispatch, in the traced window,
under `edl.h2d`: the wire cast, the stack of a dispatch's host batches and
their `device_put`, inside the timed region (worker.py) or before it
(cohort.py, the prefetcher). Innermost span wins; the five `gap_*` add up to
the named idle time (benchmark/edl_spans.py)."""

from benchmark import edl_spans


def read(run):
    return edl_spans.gap_ms(run, "h2d")
