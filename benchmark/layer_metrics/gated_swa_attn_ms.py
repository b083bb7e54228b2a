"""layer: attention kernel. Device trace, device 0: time under
`afmoe/sliding/attn` — the WINDOWED flash kernels' Mosaic custom calls
(`flash_attention_swa_fwd`, `flash_attention_swa_bwd`: the banded grids of
`ops/pallas_attention.py` at window 2048, three key blocks a query block) and
the layout changes `full_attention` makes around them — per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("afmoe/sliding/attn",))
