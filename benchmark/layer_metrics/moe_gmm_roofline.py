"""layer: sparse experts. The grouped matmuls' FLOPs by shape (forward +
backward: 6 x pairs x 3 x hidden x expert width, `expert_matmul_flops` of the
configuration's shape functions) over the chip's peak bf16 FLOP/s, over the
device time under `olmoe/moe/experts` (which also holds the SiLU and the
product between the matmuls). Compute-bound: 512 rows an expert against
weights of 12.6 MB an expert."""

from benchmark import common

_moe_ms = common.load_module("layer_metrics", "moe_ms")


def read(run):
    ms = _moe_ms.scope_ms(run, ("olmoe/moe/experts",))
    shape, peaks = run.get("shape"), run.get("peaks")
    if not ms or not shape or not peaks or "expert_matmul_flops_per_step" not in shape:
        return None
    least_s = shape["expert_matmul_flops_per_step"] / peaks["bf16_flops_per_s"]
    return 100.0 * least_s / (ms / 1e3)
