"""layer: attention kernel. The full layer's attention FLOPs by shape
(T(T + 1)/2 visible pairs a head: `global_attention_flops_per_step` of the
configuration's shape functions, counted as `swa_attn_roofline` counts) over
the chip's peak bf16 FLOP/s, over `global_attn_ms`."""

from benchmark import common

_global_attn_ms = common.load_module("layer_metrics", "global_attn_ms")
roofline = common.load_module("layer_metrics", "swa_attn_roofline").roofline


def read(run):
    return roofline(run, _global_attn_ms.read(run), "global_attention_flops_per_step")
