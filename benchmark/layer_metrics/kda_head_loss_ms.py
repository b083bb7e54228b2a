"""layer: head and loss. Device trace, device 0: time under
`kimi_linear/head_loss` (the final norm, the 20 480-wide head matmul over
16 384 positions, the float32 cross entropy, and their backward), per traced
step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("kimi_linear/head_loss",))
