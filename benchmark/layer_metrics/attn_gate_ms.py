"""layer: attention kernel. Device trace, device 0: time under the two `gate`
scopes, `afmoe/sliding/gate` and `afmoe/full/gate` — the gate's projection
h·W_g (2048 x 4096), its sigmoid and the product with attention's output,
forward, the backward's recomputation and backward — per traced step: what the
output gate costs while it is XLA's, and the most that fusing it into the
flash kernels' epilogue and backward could save of its elementwise part."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("afmoe/sliding/gate", "afmoe/full/gate"))
