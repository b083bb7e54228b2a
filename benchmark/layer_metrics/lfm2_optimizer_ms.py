"""layer: optimizer. Device trace, device 0: time under the trainer's
`optimizer` scope (the AdamW sweep over 508M parameters as far as it stands
alone: XLA fuses part of it into the backward's own fusions), per traced
step. `optimizer_ms`'s reading, bound to the LFM2 cell; it reads nothing where
the program has no `lfm2` scope."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms
traced_lfm2 = common.load_module("layer_metrics", "lfm2_flash_ms").traced_lfm2


def read(run):
    return scope_ms(run, ("optimizer",)) if traced_lfm2(run) else None
