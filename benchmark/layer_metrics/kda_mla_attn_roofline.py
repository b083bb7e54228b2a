"""layer: attention kernel. Causal attention's FLOPs by shape at heads of
(192, 128) over 16 384 keys: per head and visible (query, key) pair q·kT is
192 multiply-accumulates and p·v 128, forward + backward at 6 FLOPs a
multiply-accumulate, the masked half not counted, nothing recomputed
(`kda_mla_attention_flops_per_step` of the configuration's shape functions)
over the chip's peak bf16 FLOP/s, over `kda_mla_attn_ms`. The same kernels and
head shape as `mla_qk192_attn_roofline` reads in Xing4.0's cell, at four times
the keys. The backward kernel's recomputation of the scores is the program's
own and lowers this share."""

from benchmark import common

_ms = common.load_module("layer_metrics", "kda_mla_attn_ms")
roofline = common.load_module("layer_metrics", "swa_attn_roofline").roofline


def read(run):
    return roofline(run, _ms.read(run), "kda_mla_attention_flops_per_step")
