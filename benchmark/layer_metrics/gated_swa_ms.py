"""layer: attention kernel. Device trace, device 0: time of every operation
whose `jax.named_scope` is under `afmoe/sliding` (the four sliding-window
layers' pre-norm, q/k/v projections, the q and k head norms, rotary positions,
the banded flash kernels at window 2048, the output gate, the output projection
and the post-norm; forward, the backward's recomputation and backward), per
traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

PARTS = ("qkv", "qk_norm", "rope", "attn", "gate", "out")
SCOPES = ("afmoe/sliding",) + tuple(f"afmoe/sliding/{part}" for part in PARTS)


def read(run):
    return scope_ms(run, SCOPES)
