"""layer: multi-token prediction. Device trace, device 0: time of every
operation whose `jax.named_scope` is under `glm4_moe_lite/mtp` (the join of
the residual stream with the next token's embedding, the module's own sparse
layer — latent attention, router, held experts, shared expert — its final
norm, the shared head a second time and its cross entropy; forward,
recomputation and backward), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("scope_s"):
        return None
    return scope_ms(run, tuple(s for s in trace["scope_s"]
                               if s == "glm4_moe_lite/mtp"
                               or s.startswith("glm4_moe_lite/mtp/")))
