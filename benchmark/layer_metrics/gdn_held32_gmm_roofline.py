"""layer: sparse experts. The held experts' three grouped matmuls' FLOPs by
shape (forward + backward: 6 x pairs held x 3 x 2048 x 512,
`held_expert_matmul_flops_per_step` of the configuration's shape functions,
the pairs as the run counted them) over the chip's peak bf16 FLOP/s, over the
device time under `qwen3_next/moe/experts` (which also holds the SiLU and the
product between the matmuls, and the forward's recomputation — PERF.md §7's
warning about what that scope holds): `ops/pallas_gmm.py` at (2048, 512) and
(512, 2048), 32 groups of about 320 rows — the smallest groups and the
narrowest experts of any cell."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms
roofline = common.load_module("layer_metrics", "swa_attn_roofline").roofline


def read(run):
    return roofline(run, scope_ms(run, ("qwen3_next/moe/experts",)),
                    "held_expert_matmul_flops_per_step")
