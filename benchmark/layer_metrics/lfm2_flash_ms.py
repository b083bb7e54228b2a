"""layer: attention kernel. Device trace, device 0: summed durations of the
flash kernels' Mosaic custom calls (`flash_attention_fwd`, `flash_attention_bwd`
or `_bwd_dq` + `_bwd_dkv`: `ops/pallas_attention.py`), found by the kernels'
names — the program has them in its one attention layer only, under
`lfm2/attn/attn`: q, k, v and the output all at a head of 64, 32 query heads
on 8 key-value heads, 32 768 keys — per traced step. It reads nothing where
the program has no `lfm2` scope or no such kernel."""


def traced_lfm2(run):
    """The run's reduced trace where its program has an `lfm2` scope, else None:
    what binds a reading that names no scope of its own to this cell."""
    trace = run.get("trace") or {}
    named = any(scope.startswith("lfm2") for scope in trace.get("scope_s") or ())
    return trace if named and trace.get("steps") else None


def read(run):
    trace = traced_lfm2(run)
    if not trace or not trace.get("flash_attention_s"):
        return None
    return 1e3 * trace["flash_attention_s"] / trace["steps"]
