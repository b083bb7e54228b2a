"""layer: differential attention. Device trace, device 0: time under
`phi4flash/diff_attn` and its parts (`proj`: qkv — q alone in a cross layer —
and the out-projection; `flash`: both softmax maps in one call of the flash
kernels; `combine`: A¹v − λ·A²v, the sub-norm and its scale) of the sliding,
the full and the cross layers, forward, recomputation and backward, per traced
step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPES = ("phi4flash/diff_attn",) + tuple(
    f"phi4flash/diff_attn/{part}" for part in ("proj", "flash", "combine"))


def read(run):
    return scope_ms(run, SCOPES)
