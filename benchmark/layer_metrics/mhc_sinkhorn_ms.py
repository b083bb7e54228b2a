"""layer: hyper-connections. Device trace, device 0: time under
`xing4/mhc/sinkhorn` (the twenty rounds of row and column normalisation on a
token's 4 x 4 matrix, ten sub-blocks, and their backward), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("xing4/mhc/sinkhorn",))
