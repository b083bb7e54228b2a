"""layer: attention kernel. The attention layer's FLOPs by shape over VISIBLE
(query, key) pairs only (T(T + 1)/2 a head: q·kᵀ and p·v at 256, 16 query
heads, forward + backward at 6 FLOPs a multiply-accumulate, nothing
recomputed: `gdn_attention_flops_per_step` of the configuration's shape
functions) over the chip's peak bf16 FLOP/s, over the summed durations of the
flash kernels' Mosaic custom calls (`flash_attention_fwd`, `flash_attention_bwd`
or `_bwd_dq` + `_bwd_dkv`: `ops/pallas_attention.py`), found by the kernels'
names — the program has them in its one attention layer only, under
`qwen3_next/attn/flash` — per traced step. A block the kernel computes and
masks away and the scores' recomputation in the backward kernel are the
program's own and lower this share. It reads nothing where the program has no
`qwen3_next` scope or no such kernel."""

from benchmark import common

roofline = common.load_module("layer_metrics", "swa_attn_roofline").roofline


def traced_gdn(run):
    """The run's reduced trace where its program has a `qwen3_next` scope,
    else None: what binds a reading that names no scope of its own to this
    cell."""
    trace = run.get("trace") or {}
    named = any(scope.startswith("qwen3_next") for scope in trace.get("scope_s") or ())
    return trace if named and trace.get("steps") else None


def flash_ms(run):
    trace = traced_gdn(run)
    if not trace or not trace.get("flash_attention_s"):
        return None
    return 1e3 * trace["flash_attention_s"] / trace["steps"]


def read(run):
    return roofline(run, flash_ms(run), "gdn_attention_flops_per_step")
