"""layer: attention kernel. Device trace, device 0: summed durations of the
UNWINDOWED flash kernels' Mosaic custom calls (`flash_attention_fwd`,
`_bwd_dq`, `_bwd_dkv`) under `mellum/full/attn` — the one full-attention
layer of the period, at the sequence's whole length — per traced step."""

from benchmark import common

kernel_ms = common.load_module("layer_metrics", "swa_attn_ms").kernel_ms


def read(run):
    return kernel_ms(run, "mellum/full/attn", "flash_attention")
