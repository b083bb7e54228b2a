"""layer: trainer. The least time the chip could take for one step — the
larger of required FLOPs over peak FLOP/s and required bytes over peak
bytes/s, both from the configuration's shape functions — over `step_ms`."""


def floor_seconds(run):
    shape, peaks, w = run.get("shape"), run.get("peaks"), run["window"]
    if not shape or not peaks:
        return None
    compute = shape["model_flops_per_sample"] * w["batch"] / w["chips"] \
        / peaks["bf16_flops_per_s"]
    memory = shape["step_bytes_per_chip"] / peaks["hbm_bytes_per_s"]
    return compute, memory


def read(run):
    floors = floor_seconds(run)
    if floors is None or not run["window"].get("step_ms"):
        return None
    return 100.0 * max(floors) / (run["window"]["step_ms"] / 1e3)
