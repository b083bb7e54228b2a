"""layer: dense feed-forward. Device trace, device 0: time under
`xing4/dense_mlp` (the leading dense layer's pre-norm and its three matmuls
of 3584 x 9216, forward, recomputation and backward), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("xing4/dense_mlp",))
