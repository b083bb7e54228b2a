"""layer: hyper-connections. The least bytes the two stream mixes move
(`mhc_bytes_per_step` of the configuration's shape functions: per sub-block
the four-stream state read once and written once, forward and backward, at
the streams' stated dtype; the recomputation not counted, as no attention
roofline counts it) over the chip's peak HBM bytes/s, over `mhc_mix_ms`.
Memory-bound by shape: 24 multiply-adds a value of the state."""

from benchmark import common

_mix_ms = common.load_module("layer_metrics", "mhc_mix_ms")


def read(run):
    ms = _mix_ms.read(run)
    shape, peaks = run.get("shape"), run.get("peaks")
    if not ms or not shape or not peaks or "mhc_bytes_per_step" not in shape:
        return None
    least_s = shape["mhc_bytes_per_step"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
