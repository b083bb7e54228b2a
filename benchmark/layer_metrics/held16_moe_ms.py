"""layer: sparse experts. Device trace, device 0: time under `mellum/moe`
(pre-norm, the softmax router over 64 with its renormalised top-8, the held
dispatch, the 16 held experts' grouped matmuls at width 896 and the combine;
forward, recomputation and backward), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPES = ("mellum/moe",) + tuple(
    f"mellum/moe/{part}" for part in ("router", "dispatch", "experts", "combine"))


def read(run):
    return scope_ms(run, SCOPES)
