"""layer: state-space mixer. Device trace, device 0: time of every operation
whose `jax.named_scope` is under `nemotron_h/mamba` (pre-norm, both
projections, convolution, scan, gated norm; forward, the backward's
recomputation and backward), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

SSM_SCOPES = ("nemotron_h/mamba", "nemotron_h/mamba/in_proj", "nemotron_h/mamba/conv",
              "nemotron_h/mamba/ssd", "nemotron_h/mamba/gate_norm",
              "nemotron_h/mamba/out_proj")


def read(run):
    return scope_ms(run, SSM_SCOPES)
