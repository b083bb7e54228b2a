"""layer: attention kernel. Device trace, device 0: summed durations of the
flash kernel's Mosaic custom calls at head 256, found by the kernels' names
(`flash_attention_fwd` — twice a layer and step, the backward recomputes the
layer — `_bwd_dq`, `_bwd_dkv`), every layer's and the multi-token-prediction
module's, per traced step. The entry's `workloads` binds it to the cells
whose attention is latent: every head has a key of its own there, widened
from one shared rotary part and a per-head part."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("flash_attention_s") or not trace.get("steps"):
        return None
    return 1e3 * trace["flash_attention_s"] / trace["steps"]
