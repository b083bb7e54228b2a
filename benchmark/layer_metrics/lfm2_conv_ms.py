"""layer: gated short-convolution mixer. Device trace, device 0: time under
`lfm2/conv` — the whole mixer of the four convolution layers: the pre-norm,
the C → 3C projection (`in_proj`), the two gates' products and the depthwise
convolution between them (`gate_in`, `conv`, `gate_out`), the C → C projection
(`out_proj`); forward, recomputation and backward, per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

PARTS = ("in_proj", "gate_in", "conv", "gate_out", "out_proj")
SCOPES = ("lfm2/conv",) + tuple(f"lfm2/conv/{part}" for part in PARTS)


def read(run):
    return scope_ms(run, SCOPES)
