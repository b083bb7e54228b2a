"""layer: state-space mixer. Device trace, device 0: convolution + scan +
gated group norm (`nemotron_h/mamba/conv`, `/ssd`, `/gate_norm`), forward,
recomputation and backward, per traced step: what a layer of two projections
alone would not pay."""

from benchmark import common

_ssm_ms = common.load_module("layer_metrics", "ssm_ms")


def read(run):
    return _ssm_ms.scope_ms(run, (
        "nemotron_h/mamba/conv", "nemotron_h/mamba/ssd", "nemotron_h/mamba/gate_norm"))
