"""layer: dense feed-forward. Device trace, device 0: time under `phi4flash/mlp` (the
gated-SiLU MLP at width 10 240 of every layer: the fused gate-and-up matmul,
the gate, the down matmul; forward, recomputation and backward), per traced
step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("phi4flash/mlp",))
