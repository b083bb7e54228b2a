"""layer: delta-rule mixer. The scalar recurrence's floor — the larger of its
matmul FLOPs by the MODEL's arithmetic at a chunk of 64 (q and k at 16 heads,
v and o at 32: `delta_rule_flops_per_step` of the configuration's shape
functions, a constant of the count, not read from the program) over the chip's
peak bf16 FLOP/s and the bytes it must move (6 key planes, 5 value planes, g
and β as (T, 32): `delta_rule_bytes_per_step`) over the chip's HBM bandwidth —
over `gdn_delta_rule_ms`. `kda_delta_rule_roofline`'s form on the scalar
count: a repeated q or k, a widened g, the triangular inverse and the
backward's recomputation are the program's own and lower this share.
Memory-bound by shape: 6.48 GB against 0.77 TFLOP at 16 384 tokens."""

from benchmark import common

_ms = common.load_module("layer_metrics", "gdn_delta_rule_ms")


def read(run):
    ms = _ms.read(run)
    shape, peaks = run.get("shape"), run.get("peaks")
    if not ms or not shape or not peaks or "delta_rule_flops_per_step" not in shape:
        return None
    least_s = max(shape["delta_rule_flops_per_step"] / peaks["bf16_flops_per_s"],
                  shape["delta_rule_bytes_per_step"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
