"""layer: sparse attention. Device trace, device 0: time under
`keye/attn/select` (the exact k-th largest of every row's causal prefix by
bisection on the bit pattern, the `keep` plane, the tie rule and the
counters — not the blocks of the score plane it ranks, which `dsa_index_ms`
reads; once a layer a step: the thresholds and the plane are kept across
the layer's recomputation), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("keye/attn/select",))
