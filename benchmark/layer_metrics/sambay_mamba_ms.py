"""layer: selective-scan mixer. Device trace, device 0: time under
`phi4flash/mamba` and its parts (`proj`: the in-projection; `conv`: the
depthwise convolution and SiLU; `dt`: the x- and dt-projections and softplus;
`scan`: the selective scan; `gate_out`: the z gate and the out-projection) —
forward, recomputation and backward of the Mamba layers, the one whose scan
output is the memory among them — per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPES = ("phi4flash/mamba",) + tuple(
    f"phi4flash/mamba/{part}" for part in ("proj", "conv", "dt", "scan", "gate_out"))


def read(run):
    return scope_ms(run, SCOPES)
