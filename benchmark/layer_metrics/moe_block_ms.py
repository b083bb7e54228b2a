"""layer: sparse experts. Device trace, device 0: time of every operation
whose `jax.named_scope` is under `nemotron_h/moe` (pre-norm, router,
dispatch, the held experts' grouped matmuls, combine, the shared expert;
forward, recomputation and backward), per traced step."""

from benchmark import common

_ssm_ms = common.load_module("layer_metrics", "ssm_ms")

ROUTED_SCOPES = ("nemotron_h/moe/router", "nemotron_h/moe/dispatch",
                 "nemotron_h/moe/experts", "nemotron_h/moe/combine")


def read(run):
    return _ssm_ms.scope_ms(
        run, ("nemotron_h/moe", "nemotron_h/moe/shared") + ROUTED_SCOPES)
