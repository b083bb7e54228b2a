"""layer: looped stack. Device trace, device 0: time of every operation whose
`jax.named_scope` is under `ouro/pass` (8 layers run 4 times over shared
weights: the 32 applications' sandwich norms, projections, rotary positions,
flash kernels and MLPs and the final norm after every pass; forward, the
backward's recomputation and backward), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPES = ("ouro/pass",) + tuple(
    f"ouro/pass/{part}" for part in ("attn", "mlp", "norm", "final_norm"))


def read(run):
    return scope_ms(run, SCOPES)
