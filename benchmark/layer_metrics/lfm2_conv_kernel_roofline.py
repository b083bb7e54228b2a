"""layer: gated short-convolution mixer. The depthwise convolution ALONE at
K = 3: the bytes the two kernels of `ops/pallas_conv1d.py` must move
(`causal_conv1d_fwd` the plane read and written, `causal_conv1d_bwd` the
cotangent and the plane read and the plane's gradient written; float32,
nothing recomputed: `conv_kernel_bytes_per_step` of the configuration's shape
functions) over the chip's HBM bandwidth, over the device time under
`lfm2/conv/conv` — the scope holds the two kernels and the 8 → 1 sum of the
taps' gradient partials, (K + 1) · 8 · 2048 numbers a layer, and nothing else.
The forward kernel's second run in the recomputed layer is the program's own
and lowers this share."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms
bytes_roofline = common.load_module("layer_metrics", "lfm2_conv_mix_roofline").bytes_roofline


def read(run):
    return bytes_roofline(run, scope_ms(run, ("lfm2/conv/conv",)),
                          "conv_kernel_bytes_per_step")
