"""layer: looped stack. Device trace, device 0: time under `ouro/pass/mlp`
(the gated-SiLU MLP at width 5632 in every one of the 32 layer applications:
three matmuls, forward, recomputation and backward), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("ouro/pass/mlp",))
