"""layer: delta-rule mixer. Device trace, device 0: time under
`kimi_linear/kda/delta_rule` — everything of the recurrence: the cumulative
sums and exponentials of the per-channel decay, the chunk's (L, L) products,
the triangular inverse, the sweep of the state over the chunks, the layout
changes around them; forward, the backward's block-by-block recomputation and
backward — the four KDA layers, per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPE = ("kimi_linear/kda/delta_rule",)


def read(run):
    return scope_ms(run, SCOPE)
