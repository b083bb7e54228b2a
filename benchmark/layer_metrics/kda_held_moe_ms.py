"""layer: sparse experts. Device trace, device 0: router + dispatch + the
held experts' grouped matmuls + combine under `kimi_linear/moe`, forward,
recomputation and backward, four sparse layers, per traced step: what follows
the routing (the shared expert, which every token takes, is left out)."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

ROUTED_SCOPES = tuple(f"kimi_linear/moe/{part}"
                      for part in ("router", "dispatch", "experts", "combine"))


def read(run):
    return scope_ms(run, ROUTED_SCOPES)
