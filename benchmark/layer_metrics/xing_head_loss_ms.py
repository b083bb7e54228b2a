"""layer: head and loss. Device trace, device 0: time under `xing4/head_loss`
(the sum of the four streams, the final norm, the 16 384-wide head matmul, the
float32 cross entropy, and their backward), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("xing4/head_loss",))
