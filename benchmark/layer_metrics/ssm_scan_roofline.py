"""layer: state-space mixer. The chunked scan's floor — the larger of its
matmul FLOPs by shape over the chip's peak bf16 FLOP/s and the bytes it must
move (x, B, C, Δ read and y written forward; the same and dy read, four
gradients written backward; float32) over the chip's HBM bandwidth — over the
device time under `nemotron_h/mamba/ssd` (which also holds softplus, the
decays and D·x, and the forward's recomputation). Memory-bound by shape: 3.5
GB against 0.34 TFLOP at 8192 tokens."""

from benchmark import common

_ssm_ms = common.load_module("layer_metrics", "ssm_ms")


def read(run):
    ms = _ssm_ms.scope_ms(run, ("nemotron_h/mamba/ssd",))
    shape, peaks = run.get("shape"), run.get("peaks")
    if not ms or not shape or not peaks or "scan_flops_per_step" not in shape:
        return None
    least_s = max(shape["scan_flops_per_step"] / peaks["bf16_flops_per_s"],
                  shape["scan_bytes_per_step"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
