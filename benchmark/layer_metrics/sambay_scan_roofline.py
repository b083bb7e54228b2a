"""layer: selective-scan mixer. The selective scan's floor — the bytes it
must move (x, Δ, B, C read and y written forward; the same and dy read and
five gradients written backward; float32: `s6_scan_bytes_per_step` of the
configuration's shape functions, whatever implements the scope) over the
chip's HBM bandwidth — over `sambay_scan_ms`. `ssm_scan_roofline`'s form
without a matmul term: the recurrence has none. Its (token, channel, state
index) updates — an exponential and six vector operations each, 1.34e9 a pass
a step — are the program's own work and, with the forward's recomputation,
lower this share: the scan is bound by the vector units, not by memory."""

from benchmark import common

_ms = common.load_module("layer_metrics", "sambay_scan_ms")


def read(run):
    ms = _ms.read(run)
    shape, peaks = run.get("shape"), run.get("peaks")
    if not ms or not shape or not peaks or "s6_scan_bytes_per_step" not in shape:
        return None
    least_s = shape["s6_scan_bytes_per_step"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
