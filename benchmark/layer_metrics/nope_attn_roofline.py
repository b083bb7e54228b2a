"""layer: attention kernel. The full layer's attention FLOPs by shape
(T(T + 1)/2 visible pairs a head: `nope_attention_flops_per_step` of the
configuration's shape functions, counted as `gated_swa_attn_roofline` counts)
over the chip's peak bf16 FLOP/s, over `nope_attn_ms`."""

from benchmark import common

_ms = common.load_module("layer_metrics", "nope_attn_ms")
roofline = common.load_module("layer_metrics", "swa_attn_roofline").roofline


def read(run):
    return roofline(run, _ms.read(run), "nope_attention_flops_per_step")
