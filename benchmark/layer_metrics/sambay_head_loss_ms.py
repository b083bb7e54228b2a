"""layer: head and loss. Device trace, device 0: time under
`phi4flash/head_loss` (the final LayerNorm, the TIED head's matmul against the
embedding itself, the cross entropy over the vocabulary slice and their
backward) and `phi4flash/embed` (the gather and, backward, the scatter-add
into the same matrix), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("phi4flash/head_loss", "phi4flash/embed"))
