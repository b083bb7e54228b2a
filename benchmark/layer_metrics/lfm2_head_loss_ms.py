"""layer: head and loss. Device trace, device 0: time under `lfm2/head_loss`
(the final norm, the tied head — the 16 384-row embedding as a matmul over
32 768 positions — the float32 cross entropy, and their backward), per traced
step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("lfm2/head_loss",))
