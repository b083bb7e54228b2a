"""layer: attention kernel. Device trace, device 0: time of every operation
whose `jax.named_scope` is under `mellum/sliding` (the three sliding-window
layers' pre-norm, q/k/v projections, rotary positions from the plain table,
the banded flash kernels and the output projection; forward, the backward's
recomputation and backward), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPES = ("mellum/sliding",) + tuple(
    f"mellum/sliding/{part}" for part in ("qkv", "rope", "attn", "out"))


def read(run):
    return scope_ms(run, SCOPES)
