"""layer: attention kernel. The sliding layers' attention FLOPs by shape over
VISIBLE (query, key) pairs only (Σ_i min(i + 1, 2048) a head: q·kT and p·v,
forward + backward at 6 FLOPs a multiply-accumulate, nothing recomputed:
`gated_swa_attention_flops_per_step` of the configuration's shape functions)
over the chip's peak bf16 FLOP/s, over `gated_swa_attn_ms`. At 1024-blocks the
band is 45 blocks a head of which 67% of the pairs are visible; the masked
third, the scores' recomputation in the backward kernel and a forward kernel
run again by the layer's recomputation are the program's own and lower this
share."""

from benchmark import common

_ms = common.load_module("layer_metrics", "gated_swa_attn_ms")
roofline = common.load_module("layer_metrics", "swa_attn_roofline").roofline


def read(run):
    return roofline(run, _ms.read(run), "gated_swa_attention_flops_per_step")
