"""layer: attention kernel. The attention FLOPs by shape over SELECTED (query,
key) pairs only (Σ_t min(t + 1, K) a head: q·kT and p·v, forward + backward at
6 FLOPs a multiply-accumulate, nothing recomputed:
`dsa_attention_flops_per_step` of the configuration's shape functions) over
the chip's peak bf16 FLOP/s, over `dsa_attn_ms`. The masked kernels compute
every causal pair and mask three quarters of them away: those pairs are not in
the count, so they lower this share."""

from benchmark import common

roofline = common.load_module("layer_metrics", "swa_attn_roofline").roofline
_dsa_attn_ms = common.load_module("layer_metrics", "dsa_attn_ms")


def read(run):
    return roofline(run, _dsa_attn_ms.read(run), "dsa_attention_flops_per_step")
