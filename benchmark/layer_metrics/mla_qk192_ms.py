"""layer: latent attention. Device trace, device 0: time of every operation
whose `jax.named_scope` is under `xing4/mla` (pre-norm, the low-rank query
and key-value paths with their inner norms, YaRN's rotation, the flash kernels
at q/k heads of 192 and v heads of 128, the output projection; forward, the
backward's recomputation and backward), five layers, per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

MLA_SCOPES = tuple(f"xing4/{part}" for part in (
    "mla", "mla/q_lora", "mla/kv_lora", "mla/rope", "mla/attn", "mla/out"))


def read(run):
    return scope_ms(run, MLA_SCOPES)
