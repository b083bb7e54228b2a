"""layer: attention kernel. Device trace, device 0: summed durations of the
flash kernels with a data mask (`flash_attention_sel_fwd`, `_sel_bwd`; split
route: `_sel_bwd_dq`, `_sel_bwd_dkv`) under `keye/attn/attn`, per traced step.
A run of a program without such kernels reads nothing."""

from benchmark import common

kernel_ms = common.load_module("layer_metrics", "swa_attn_ms").kernel_ms

SCOPE, PREFIX = "keye/attn/attn", "flash_attention_sel"


def read(run):
    return kernel_ms(run, SCOPE, PREFIX)
