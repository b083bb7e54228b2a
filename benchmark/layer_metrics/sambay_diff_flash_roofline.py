"""layer: attention kernel. Differential attention's FLOPs by shape over
VISIBLE (query, key) pairs only (T(T + 1)/2 a head in the full and the cross
layers, Σ_t min(t + 1, W) in the sliding one: both maps' q·kᵀ at 64 and p·v at
128, forward + backward at 6 FLOPs a multiply-accumulate, nothing recomputed:
`diff_flash_flops_per_step` of the configuration's shape functions) over the
chip's peak bf16 FLOP/s, over `sambay_diff_flash_ms`. A block the kernel
computes and masks away (three quarters of the sliding layer's, at blocks of
1024 under a window of 512), the scores' recomputation in the backward kernel
and a q·kᵀ that fills half the MXU's depth are the program's own and lower
this share."""

from benchmark import common

_ms = common.load_module("layer_metrics", "sambay_diff_flash_ms")
roofline = common.load_module("layer_metrics", "swa_attn_roofline").roofline


def read(run):
    return roofline(run, _ms.read(run), "diff_flash_flops_per_step")
