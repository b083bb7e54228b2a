"""layer: attention kernel. Device trace, device 0: summed durations of the
flash kernel's Mosaic custom calls in their grouped-query form, found by the
kernels' names (`flash_attention_fwd` — twice a step, the backward recomputes
the block — `_bwd_dq`, `_bwd_dkv`), per traced step. The entry's `workloads`
binds it to the cells whose attention is grouped-query; `attn_ms` is the same
sum for the cells whose every query head has its own key-value head."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("flash_attention_s") or not trace.get("steps"):
        return None
    return 1e3 * trace["flash_attention_s"] / trace["steps"]
