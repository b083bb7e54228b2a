"""layer: optimizer. Device trace, device 0: time under the trainer's
`optimizer` scope (the AdamW sweep over 697M parameters, the tied matrix once,
as far as it stands alone: XLA fuses part of it into the backward's own
fusions), per traced step. `optimizer_ms`'s reading, bound to the Phi-4-mini-
flash cell."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("optimizer",))
