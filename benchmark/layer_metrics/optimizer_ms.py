"""layer: optimizer. Device trace, device 0: time under the trainer's
`optimizer` scope (the AdamW sweep over every parameter), per traced step."""

from benchmark import common

_moe_ms = common.load_module("layer_metrics", "moe_ms")


def read(run):
    return _moe_ms.scope_ms(run, ("optimizer",))
