"""layer: latent attention. Device trace, device 0: time of every operation
whose `jax.named_scope` is under `glm4_moe_lite/mla` or, for the
multi-token-prediction module's own layer, `glm4_moe_lite/mtp/mla` (pre-norm,
the low-rank query and key-value paths with their inner norms, rotary
positions, the flash kernel, the output projection; forward, the backward's
recomputation and backward), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

_PARTS = ("mla", "mla/q_lora", "mla/kv_lora", "mla/rope", "mla/attn", "mla/out")
MLA_SCOPES = tuple(f"glm4_moe_lite/{stream}{part}"
                   for stream in ("", "mtp/") for part in _PARTS)


def read(run):
    return scope_ms(run, MLA_SCOPES)
