"""layer: sparse experts. Device trace, device 0: router + dispatch + the
held experts' grouped matmuls + combine under `glm4_moe_lite/moe` and
`glm4_moe_lite/mtp/moe`, forward, recomputation and backward, per traced
step: what follows the routing (the shared expert, which every token takes,
is left out)."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

ROUTED_SCOPES = tuple(f"glm4_moe_lite/{stream}moe/{part}" for stream in ("", "mtp/")
                      for part in ("router", "dispatch", "experts", "combine"))


def read(run):
    return scope_ms(run, ROUTED_SCOPES)
