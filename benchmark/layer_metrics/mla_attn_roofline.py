"""layer: attention kernel. Causal attention's FLOPs by shape at head 256
(q·kT and p·v of each of the 20 heads in every layer and in the module's,
forward + backward at 6 FLOPs a multiply-accumulate, the masked half not
counted, nothing recomputed: `mla_attention_flops_per_step` of the
configuration's shape functions) over the chip's peak bf16 FLOP/s, over
`mla_attn_ms`. The kernel recomputes the scores in each of its two backward
passes and the layer's recomputation runs the forward kernel a second time;
that work is the program's own and is not counted."""

from benchmark import common

_mla_attn_ms = common.load_module("layer_metrics", "mla_attn_ms")


def read(run):
    ms = _mla_attn_ms.read(run)
    shape, peaks = run.get("shape"), run.get("peaks")
    if not ms or not shape or not peaks or "mla_attention_flops_per_step" not in shape:
        return None
    least_s = shape["mla_attention_flops_per_step"] / peaks["bf16_flops_per_s"]
    return 100.0 * least_s / (ms / 1e3)
