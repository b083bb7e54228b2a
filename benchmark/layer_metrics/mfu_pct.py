"""layer: trainer. Shape-derived model FLOPs per sample (forward + backward,
nothing recomputed) times samples per second per chip, over the chip's peak
bf16 FLOP/s."""


def read(run):
    shape, peaks, w = run.get("shape"), run.get("peaks"), run["window"]
    if not shape or not peaks:
        return None
    rate = w["samples_per_s"] / w["chips"]
    return 100.0 * shape["model_flops_per_sample"] * rate / peaks["bf16_flops_per_s"]
