"""layer: embedding engine. Device trace: summed durations of the Mosaic
custom calls (the placement kernel of the embedding backward) on device 0,
per traced step."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("mosaic_calls") or not trace.get("steps"):
        return None
    return 1e3 * trace["mosaic_s"] / trace["steps"]
