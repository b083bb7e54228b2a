"""layer: attention kernel. Device trace, device 0: summed durations of the
flash kernels' Mosaic custom calls at TWO head widths (q and k 192, v and the
output 128), found by the kernels' names (`flash_attention_fwd`, `_bwd`): the
program's only flash calls, all under `kimi_linear/mla/attn`, per traced step.
Read only where the program has the `kimi_linear` scopes."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("flash_attention_s") or not trace.get("steps"):
        return None
    if not any(scope.startswith("kimi_linear/mla") for scope in trace.get("scope_s") or ()):
        return None
    return 1e3 * trace["flash_attention_s"] / trace["steps"]
