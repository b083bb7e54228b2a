"""layer: attention kernel. The attention layer's FLOPs by shape over VISIBLE
(query, key) pairs only (T(T + 1)/2 a head: q·kᵀ and p·v at 64, forward +
backward at 6 FLOPs a multiply-accumulate, nothing recomputed:
`attn_flops_per_step` of the configuration's shape functions) over the chip's
peak bf16 FLOP/s, over `lfm2_flash_ms`. A block the kernel computes and masks
away (half of each diagonal block), the scores' recomputation in the backward
kernel, a q·kᵀ that contracts over 64 of the MXU's 128 and an output half a
lane tile wide are the program's own and lower this share."""

from benchmark import common

_ms = common.load_module("layer_metrics", "lfm2_flash_ms")
roofline = common.load_module("layer_metrics", "swa_attn_roofline").roofline


def read(run):
    return roofline(run, _ms.read(run), "attn_flops_per_step")
