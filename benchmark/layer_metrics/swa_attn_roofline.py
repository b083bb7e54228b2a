"""layer: attention kernel. The sliding layers' attention FLOPs by shape over
VISIBLE (query, key) pairs only (Σ_i min(i + 1, W) a head: q·kT and p·v,
forward + backward at 6 FLOPs a multiply-accumulate, nothing recomputed:
`swa_attention_flops_per_step` of the configuration's shape functions) over
the chip's peak bf16 FLOP/s, over `swa_attn_ms`. A block the kernel computes
and masks away (half of each edge block of the band) is not in the count, and
neither is the scores' recomputation in the two backward kernels: both lower
this share."""

from benchmark import common

_swa_attn_ms = common.load_module("layer_metrics", "swa_attn_ms")

FLOPS = "swa_attention_flops_per_step"


def roofline(run, ms, flops_key):
    shape, peaks = run.get("shape"), run.get("peaks")
    if not ms or not shape or not peaks or flops_key not in shape:
        return None
    least_s = shape[flops_key] / peaks["bf16_flops_per_s"]
    return 100.0 * least_s / (ms / 1e3)


def read(run):
    return roofline(run, _swa_attn_ms.read(run), FLOPS)
