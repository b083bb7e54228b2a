"""layer: delta-rule mixer. Device trace, device 0: time under
`qwen3_next/gdn/delta_rule` — everything of the scalar recurrence: the
cumulative sums of the (T, 32) log-decay, the two Pallas kernels
(`delta_rule_scalar_fwd` once a step, its residuals kept; `_bwd`, which
recomputes a block's chunk algebra), the layout changes of Γ and β around
them — the three Gated DeltaNet layers, per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPE = ("qwen3_next/gdn/delta_rule",)


def read(run):
    return scope_ms(run, SCOPE)
