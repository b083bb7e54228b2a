"""layer: sparse experts. Device trace, device 0: router + dispatch + the
held experts' grouped matmuls + combine, forward, recomputation and backward,
per traced step: what follows the routing (the shared expert, which every
token takes, is left out)."""

from benchmark import common

_ssm_ms = common.load_module("layer_metrics", "ssm_ms")
_moe_block_ms = common.load_module("layer_metrics", "moe_block_ms")


def read(run):
    return _ssm_ms.scope_ms(run, _moe_block_ms.ROUTED_SCOPES)
