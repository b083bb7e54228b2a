"""layer: attention kernel. Causal grouped-query attention's FLOPs by shape
(q·kT and p·v of every QUERY head, forward + backward at 6 FLOPs a
multiply-accumulate, the masked half not counted, nothing recomputed) over
the chip's peak bf16 FLOP/s, over `gqa_attn_ms`. The kernel recomputes the
scores in each of its two backward passes and the block's recomputation runs
the forward kernel a second time; that work is the program's own and is not
counted."""

from benchmark import common

_gqa_attn_ms = common.load_module("layer_metrics", "gqa_attn_ms")


def read(run):
    ms = _gqa_attn_ms.read(run)
    shape, peaks = run.get("shape"), run.get("peaks")
    if not ms or not shape or not peaks or "gqa_attention_flops_per_step" not in shape:
        return None
    least_s = shape["gqa_attention_flops_per_step"] / peaks["bf16_flops_per_s"]
    return 100.0 * least_s / (ms / 1e3)
