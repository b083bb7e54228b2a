"""layer: sparse attention. Device trace, device 0: time under
`keye/attn/index` (the indexer's three projections, the key layernorm, rotary
positions, and their backward) and under the `scores` scope wherever a block of
the (T, T) score plane is made (`ops/sparse_attention.py::_score_block`: for
the selection, for the index loss and again, with its pull-back, in the index
loss's backward), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("keye/attn/index", "keye/attn/select/scores", "keye/attn/index_loss/scores"))
