"""layer: sparse experts. Device trace, device 0: time under `keye/moe`
(pre-norm, the softmax router over 128 with its renormalised top-8, the held
dispatch, the 16 held experts' grouped matmuls at width 768 and the combine;
forward, recomputation and backward), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPES = ("keye/moe",) + tuple(
    f"keye/moe/{part}" for part in ("router", "dispatch", "experts", "combine"))


def read(run):
    return scope_ms(run, SCOPES)
