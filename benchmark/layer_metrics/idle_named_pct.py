"""layer: device. Device trace and the program's own spans in it: of device
0's idle time in the window, the share that lies under any `edl.*` span of
the task loop's thread (benchmark/edl_spans.py). What is left lies under no
span: before the first or after the last recorded one, or in a process the
program does not annotate."""

from benchmark import edl_spans


def read(run):
    f = edl_spans.figures(run)
    if f is None or not f["idle_ns"]:
        return None
    return 100.0 * f["named_ns"] / f["idle_ns"]
