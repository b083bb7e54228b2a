"""layer: sparse experts. Device trace, device 0: time under `afmoe/moe`
(pre-norm, the sigmoid router over 128 with its selection bias and renormalised
top-8, the held dispatch, the 16 held experts' grouped matmuls at width 1024,
the combine, the shared expert and the post-norm; forward, recomputation and
backward), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPES = ("afmoe/moe",) + tuple(
    f"afmoe/moe/{part}" for part in ("router", "shared", "dispatch", "experts", "combine"))


def read(run):
    return scope_ms(run, SCOPES)
