"""layer: attention kernel. Device trace, device 0: summed durations of the
causal flash kernels' Mosaic custom calls, found by the kernels' names
(`flash_attention_fwd`, `_bwd`: `ops/pallas_attention.py`) under
`ouro/pass/attn` — 32 layer applications a step, and the forward kernel again
in every application whose residuals are not kept — per traced step. A run of
a program without such kernels reads nothing."""

from benchmark import common

kernel_ms = common.load_module("layer_metrics", "swa_attn_ms").kernel_ms

SCOPE, PREFIX = "ouro/pass/attn", "flash_attention"


def read(run):
    return kernel_ms(run, SCOPE, PREFIX)
