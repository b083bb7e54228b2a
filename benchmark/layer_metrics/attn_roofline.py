"""layer: attention kernel. Causal attention's FLOPs by shape (q·kT and p·v,
forward + backward at 6 FLOPs a multiply-accumulate, the masked half not
counted, nothing recomputed) over the chip's peak bf16 FLOP/s, over
`attn_ms`. Compute-bound at 4096 tokens. The kernel recomputes the scores in
each of its two backward passes; that work is its own and is not counted."""

from benchmark import common

_attn_ms = common.load_module("layer_metrics", "attn_ms")


def read(run):
    ms = _attn_ms.read(run)
    shape, peaks = run.get("shape"), run.get("peaks")
    if not ms or not shape or not peaks or "attention_flops_per_step" not in shape:
        return None
    least_s = shape["attention_flops_per_step"] / peaks["bf16_flops_per_s"]
    return 100.0 * least_s / (ms / 1e3)
