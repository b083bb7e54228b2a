"""layer: latent attention. Device trace, device 0: time of every operation
whose `jax.named_scope` is under `kimi_linear/mla` (pre-norm, the one query
projection, the low-rank key-value path with its inner norm, the
concatenation of the unrotated parts, the flash kernels at q/k heads of 192
and v heads of 128 over 16 384 keys, the output projection; forward, the
backward's recomputation and backward), the one latent layer, per traced
step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

MLA_SCOPES = tuple(f"kimi_linear/{part}" for part in (
    "mla", "mla/q_proj", "mla/kv_lora", "mla/rope", "mla/attn", "mla/out"))


def read(run):
    return scope_ms(run, MLA_SCOPES)
