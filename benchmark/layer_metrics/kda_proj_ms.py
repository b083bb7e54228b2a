"""layer: delta-rule mixer. Device trace, device 0: the mixer's four wide
matmuls — `kimi_linear/kda/proj` (q, k, v: 2304 -> 4096 each) and `/out`
(4096 -> 2304) — forward, recomputation and backward, the four KDA layers,
per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPES = ("kimi_linear/kda/proj", "kimi_linear/kda/out")


def read(run):
    return scope_ms(run, SCOPES)
