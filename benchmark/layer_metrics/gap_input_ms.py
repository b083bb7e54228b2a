"""layer: input. Device 0's idle time per dispatch, in the traced window, under
`edl.data_wait`: the task loop waiting for a host batch from the reader and
the parse pool, the first pull of each task included. Innermost span wins;
the five `gap_*` add up to the named idle time (benchmark/edl_spans.py)."""

from benchmark import edl_spans


def read(run):
    return edl_spans.gap_ms(run, "input")
