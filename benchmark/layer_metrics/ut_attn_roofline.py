"""layer: attention kernel. Causal attention's FLOPs by shape over VISIBLE
(query, key) pairs only (T(T + 1)/2 a head: q·kT and p·v, forward + backward
at 6 FLOPs a multiply-accumulate, in every one of the 32 layer applications,
nothing recomputed: `ut_attention_flops_per_step` of the configuration's shape
functions) over the chip's peak bf16 FLOP/s, over `ut_attn_ms`. The kernels
and the shape are `attn_roofline`'s in the OLMoE cell; a forward kernel run
again in a recomputed application and the scores' recomputation in the
backward kernel are the program's own and lower this share."""

from benchmark import common

_ut_attn_ms = common.load_module("layer_metrics", "ut_attn_ms")
roofline = common.load_module("layer_metrics", "swa_attn_roofline").roofline

FLOPS = "ut_attention_flops_per_step"


def read(run):
    return roofline(run, _ut_attn_ms.read(run), FLOPS)
