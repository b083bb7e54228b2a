"""layer: attention kernel. Device trace, device 0: summed durations of the
flash kernel's Mosaic custom calls, found by the kernels' names
(`flash_attention_fwd`, `_bwd_dq`, `_bwd_dkv`), per traced step."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("flash_attention_s") or not trace.get("steps"):
        return None
    return 1e3 * trace["flash_attention_s"] / trace["steps"]
