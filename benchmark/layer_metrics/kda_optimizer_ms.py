"""layer: optimizer. Device trace, device 0: time under the trainer's
`optimizer` scope (the AdamW sweep over 602M parameters as far as it stands
alone: XLA fuses part of it into the backward's own fusions), per traced
step. `optimizer_ms`'s reading, bound to the Kimi Linear cell; it reads
nothing where the program has no `kimi_linear` scope."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    trace = run.get("trace") or {}
    if not any(scope.startswith("kimi_linear") for scope in trace.get("scope_s") or ()):
        return None
    return scope_ms(run, ("optimizer",))
