"""layer: attention kernel. Device trace, device 0: summed durations of the
WINDOWED flash kernels' Mosaic custom calls, found by the kernels' names
(`flash_attention_swa_fwd`, `_swa_bwd_dq`, `_swa_bwd_dkv`: the banded grids
of `ops/pallas_attention.py`) under `mellum/sliding/attn`, per traced step.
A run of a program without such kernels reads nothing."""

SCOPE, PREFIX = "mellum/sliding/attn", "flash_attention_swa"


def kernel_ms(run, scope, prefix):
    """ms a traced step in the kernels named `prefix…` under `scope`, from a
    driver's `kernel_s` ({scope: {prefix: seconds}}), or None."""
    trace = run.get("trace")
    if not trace or not trace.get("steps"):
        return None
    seconds = (trace.get("kernel_s") or {}).get(scope, {}).get(prefix)
    return 1e3 * seconds / trace["steps"] if seconds else None


def read(run):
    return kernel_ms(run, SCOPE, PREFIX)
