"""layer: attention kernel. Device trace, device 0: time under
`afmoe/full/attn` — the UNWINDOWED flash kernels (`flash_attention_fwd`,
`flash_attention_bwd`) of the one full-attention layer, whose q and k are
normalised and carry NO rotary positions, at the sequence's whole length, and
the layout changes around them — per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("afmoe/full/attn",))
