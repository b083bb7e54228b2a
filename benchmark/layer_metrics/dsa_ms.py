"""layer: sparse attention. Device trace, device 0: time of every operation
whose `jax.named_scope` is under `keye/attn` (pre-norm, q/k/v projections with
their per-head norms, rotary positions, the indexer and its score plane, the
selection, the masked flash kernels, the index loss and the output
projection; forward, the backward's recomputation and backward), per traced
step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPES = ("keye/attn",) + tuple(f"keye/attn/{part}" for part in (
    "qkv", "rope", "index", "select", "select/scores", "attn", "index_loss",
    "index_loss/scores", "out"))


def read(run):
    return scope_ms(run, SCOPES)
