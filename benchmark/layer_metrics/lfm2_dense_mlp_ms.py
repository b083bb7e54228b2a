"""layer: dense feed-forward. Device trace, device 0: time under
`lfm2/dense_mlp` (the leading dense layer's pre-norm and its three matmuls of
2048 x 7168 over 32 768 tokens, forward, recomputation and backward), per
traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("lfm2/dense_mlp",))
