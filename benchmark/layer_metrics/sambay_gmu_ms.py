"""layer: gated memory unit. Device trace, device 0: time under
`phi4flash/gmu` (the gate's projection, the memory's element-wise gate and the
out-projection of every GMU layer: forward, recomputation and backward; the
memory itself is read, not made again), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("phi4flash/gmu",))
