"""layer: sparse experts. Device trace, device 0: router + dispatch + the held
experts' grouped matmuls + combine under `lfm2/moe` (the model has no shared
expert), forward, recomputation and backward, four sparse layers, per traced
step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPES = ("lfm2/moe",) + tuple(
    f"lfm2/moe/{part}" for part in ("router", "dispatch", "experts", "combine"))


def read(run):
    return scope_ms(run, SCOPES)
