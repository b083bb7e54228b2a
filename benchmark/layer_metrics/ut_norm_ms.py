"""layer: looped stack. Device trace, device 0: time under `ouro/pass/norm`
and `ouro/pass/final_norm` (the four float32 sandwich RMSNorms of every layer
application with the residual adds, and the final norm after every pass;
forward, recomputation and backward), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("ouro/pass/norm", "ouro/pass/final_norm"))
