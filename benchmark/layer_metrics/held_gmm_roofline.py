"""layer: sparse experts. The held experts' three grouped matmuls' FLOPs by
shape (forward + backward: 6 x pairs held x 3 x hidden x expert width,
`held_expert_matmul_flops_per_step` of the configuration's shape functions,
the pairs as the run counted them) over the chip's peak bf16 FLOP/s, over the
device time under `glm4_moe_lite/moe/experts` and `glm4_moe_lite/mtp/moe/
experts` (which also hold the SiLU and the product between the matmuls, and
the forward's recomputation): `ops/pallas_gmm.py` at (2048, 1536) and (1536,
2048), about 512 rows an expert."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    ms = scope_ms(run, ("glm4_moe_lite/moe/experts", "glm4_moe_lite/mtp/moe/experts"))
    shape, peaks = run.get("shape"), run.get("peaks")
    if not ms or not shape or not peaks or "held_expert_matmul_flops_per_step" not in shape:
        return None
    least_s = shape["held_expert_matmul_flops_per_step"] / peaks["bf16_flops_per_s"]
    return 100.0 * least_s / (ms / 1e3)
