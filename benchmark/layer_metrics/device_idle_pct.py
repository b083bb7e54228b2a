"""layer: device. Device trace of a steady window: 1 - union of the intervals
in which an operation runs / window, averaged over the chips used."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
