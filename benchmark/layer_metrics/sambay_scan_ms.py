"""layer: selective-scan mixer. Device trace, device 0: time under
`phi4flash/mamba/scan` — the S6 recurrence (`ops/ssm.py::selective_scan`: on
the chip the kernels `selective_scan_fwd`, twice a layer with the
recomputation, and `selective_scan_bwd`, and XLA's transposes of B, C and
their gradients around them), A = −exp(A_log) and D·x — per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPE = "phi4flash/mamba/scan"


def read(run):
    return scope_ms(run, (SCOPE,))
