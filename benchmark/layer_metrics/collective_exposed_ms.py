"""layer: parallelism. Device trace, device 0: the part of the collectives'
time during which no other operation runs on that device, per traced step."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("steps") or run["window"]["chips"] < 2:
        return None
    return 1e3 * trace["collective_exposed_s"] / trace["steps"]
