"""layer: trainer. Device 0's idle time per dispatch, in the traced window, under
`edl.compute` less the `edl.h2d` inside it: the enqueue
(`edl.compute.dispatch`) and the tail between the device finishing and the
host seeing it (`edl.compute.readback`). Innermost span wins; the five
`gap_*` add up to the named idle time (benchmark/edl_spans.py)."""

from benchmark import edl_spans


def read(run):
    return edl_spans.gap_ms(run, "step")
