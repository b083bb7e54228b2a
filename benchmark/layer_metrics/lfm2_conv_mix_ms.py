"""layer: gated short-convolution mixer. Device trace, device 0: the mixer's
elementwise part, `G ⊙ conv₃(B ⊙ u)` — the scopes `lfm2/conv/gate_in` (XLA's
product of two column blocks of the projection), `/conv` (the two kernels of
`ops/pallas_conv1d.py` at K = 3 on a TPU) and `/gate_out` (XLA's product) —
bound by the bytes of its (T, 2048) float32 planes; forward, recomputation and
backward, the four convolution layers, per traced step. What a fusion of the
gates into the kernels would shrink."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SCOPES = tuple(f"lfm2/conv/{part}" for part in ("gate_in", "conv", "gate_out"))


def read(run):
    return scope_ms(run, SCOPES)
