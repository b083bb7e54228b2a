"""layer: parallelism. Device trace, device 0: union of the collective
operations' intervals (all-reduce, all-gather, all-to-all, reduce-scatter,
collective-permute), per traced step."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("steps") or run["window"]["chips"] < 2:
        return None
    return 1e3 * trace["collective_s"] / trace["steps"]
