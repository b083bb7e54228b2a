"""layer: delta-rule mixer. Device trace, device 0: time of every operation
whose `jax.named_scope` is under `kimi_linear/kda` (pre-norm, the q, k, v
projections, the three convolutions and SiLU, both low-rank gates, β and the
decay, the L2 norms, the recurrence, the output norm and gate, the output
projection; forward, the backward's recomputation and backward), the four KDA
layers, per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

KDA_SCOPES = tuple(f"kimi_linear/{part}" for part in (
    "kda", "kda/proj", "kda/conv", "kda/gates", "kda/qk_norm", "kda/delta_rule",
    "kda/out_gate", "kda/out", "kda/counters"))


def read(run):
    return scope_ms(run, KDA_SCOPES)
