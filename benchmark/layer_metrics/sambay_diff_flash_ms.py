"""layer: attention kernel. Device trace, device 0: summed durations of the
flash kernels' Mosaic custom calls, found by the kernels' names
(`flash_attention_fwd`, `_bwd`, and the windowed `flash_attention_swa_*`:
`ops/pallas_attention.py`) under `phi4flash/diff_attn/flash` — one call a
layer and pass for BOTH softmax maps, q/k heads of 64 against v heads of 128 —
per traced step. A run of a program without such kernels reads nothing."""

from benchmark import common

kernel_ms = common.load_module("layer_metrics", "swa_attn_ms").kernel_ms

SCOPE, PREFIX = "phi4flash/diff_attn/flash", "flash_attention"


def read(run):
    return kernel_ms(run, SCOPE, PREFIX)
