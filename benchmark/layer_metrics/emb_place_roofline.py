"""layer: embedding engine. Bytes the placement needs (sorted gradient stream
read once, the dense gradient written once; `placement_bytes` of the
configuration's shape functions) over peak bytes/s, over the placement
kernel's own time (`emb_place_ms`)."""

from benchmark import common

traced_kernel = common.load_module("layer_metrics", "emb_place_ms").traced_kernel


def read(run):
    shape, peaks, traced = run.get("shape"), run.get("peaks"), traced_kernel(run)
    if not shape or not peaks or traced is None:
        return None
    seconds, steps = traced
    least = shape["placement_bytes_per_chip"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)
