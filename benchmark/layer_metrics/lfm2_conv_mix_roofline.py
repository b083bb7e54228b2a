"""layer: gated short-convolution mixer. The floor of the mixer's elementwise
part — the bytes `G ⊙ conv₃(B ⊙ u)` must move as ONE pass a direction
(B, G, u read and the result written forward; the same three and the
cotangent read and three gradients written backward; float32, nothing
recomputed: `gated_conv_bytes_per_step` of the configuration's shape
functions, whatever implements the three scopes) over the chip's HBM
bandwidth — over `lfm2_conv_mix_ms`. The unfused products either side of the
kernels (five planes read and three written a forward pass beside the
kernel's own two) and the forward's recomputation are the program's own and
lower this share: a later fusion is judged by the same yardstick."""

from benchmark import common

_ms = common.load_module("layer_metrics", "lfm2_conv_mix_ms")


def bytes_roofline(run, ms, bytes_key):
    shape, peaks = run.get("shape"), run.get("peaks")
    if not ms or not shape or not peaks or bytes_key not in shape:
        return None
    least_s = shape[bytes_key] / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)


def read(run):
    return bytes_roofline(run, _ms.read(run), "gated_conv_bytes_per_step")
