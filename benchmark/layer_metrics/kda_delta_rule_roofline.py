"""layer: delta-rule mixer. The recurrence's floor — the larger of its matmul
FLOPs by the MODEL's arithmetic at a chunk of 64 (`delta_rule_flops_per_step`
of the configuration's shape functions: a constant of the count, not read from
the program) over the chip's peak bf16 FLOP/s and the bytes it must move (q,
k, v, g read and o written forward; the same and do read, four gradients
written backward; float32: `delta_rule_bytes_per_step`) over the chip's HBM
bandwidth — over `kda_delta_rule_ms`. `ssm_scan_roofline`'s form, and the same
count whatever implements the scope: today XLA's batched matmuls and two
scans, whose recomputation, triangular inverse and layout changes are the
program's own and lower this share. Memory-bound by shape: 16.1 GB against
1.13 TFLOP at 16 384 tokens."""

from benchmark import common

_ms = common.load_module("layer_metrics", "kda_delta_rule_ms")


def read(run):
    ms = _ms.read(run)
    shape, peaks = run.get("shape"), run.get("peaks")
    if not ms or not shape or not peaks or "delta_rule_flops_per_step" not in shape:
        return None
    least_s = max(shape["delta_rule_flops_per_step"] / peaks["bf16_flops_per_s"],
                  shape["delta_rule_bytes_per_step"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
