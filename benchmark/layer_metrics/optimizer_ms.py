"""layer: optimizer. Device trace, device 0: time under the trainer's
`optimizer` scope (the AdamW sweep over every parameter, a tied matrix once,
as far as it stands alone: XLA fuses part of it into the backward's own
fusions), per traced step. One reader for every model: `BENCHMARK.json` lists
the cells that print it."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("optimizer",))
