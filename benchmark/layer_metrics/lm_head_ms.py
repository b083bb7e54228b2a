"""layer: head and loss. Device trace, device 0: time under `olmoe/head_loss`
(final norm, the vocabulary-wide head matmul, the float32 cross entropy, and
their backward), per traced step."""

from benchmark import common

_moe_ms = common.load_module("layer_metrics", "moe_ms")


def read(run):
    return _moe_ms.scope_ms(run, ("olmoe/head_loss",))
