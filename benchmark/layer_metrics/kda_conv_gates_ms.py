"""layer: delta-rule mixer. Device trace, device 0: the mixer's elementwise
and low-rank parts around the recurrence — `kimi_linear/kda/conv` (three
depthwise convolutions of width 4 and SiLU), `/gates` (both low-rank gates'
first halves, β, softplus and the decay), `/qk_norm` (the L2 norms) and
`/out_gate` (the per-head RMSNorm, the gate's second half and its sigmoid):
bound by the bytes of their (T, 4096) float32 planes; forward, recomputation
and backward, the four KDA layers, per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPES = tuple(f"kimi_linear/kda/{part}" for part in ("conv", "gates", "qk_norm", "out_gate"))


def read(run):
    return scope_ms(run, SCOPES)
