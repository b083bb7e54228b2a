"""layer: embedding engine. Bytes the placement needs (sorted gradient stream
read once, the dense gradient written once; `placement_bytes` of the
configuration's shape functions) over peak bytes/s, over `emb_place_ms`."""


def read(run):
    trace, shape, peaks = run.get("trace"), run.get("shape"), run.get("peaks")
    if not trace or not shape or not peaks or not trace.get("mosaic_calls") \
            or not trace.get("steps"):
        return None
    least = shape["placement_bytes_per_chip"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least / (trace["mosaic_s"] / trace["steps"])
