"""layer: attention kernel. Device trace, device 0: time under `lfm2/attn` —
the one attention layer whole: the pre-norm, the q, k, v projections (`qkv`),
the head norms (`qk_norm`), the rotation (`rope`), the flash kernels (`attn`)
and the output projection (`out`); forward, recomputation (the flash forward
kernel's residuals are kept, so it runs once) and backward, per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPES = ("lfm2/attn",) + tuple(
    f"lfm2/attn/{part}" for part in ("qkv", "qk_norm", "rope", "attn", "out"))


def read(run):
    return scope_ms(run, SCOPES)
