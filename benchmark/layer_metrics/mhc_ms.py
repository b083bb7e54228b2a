"""layer: hyper-connections. Device trace, device 0: time of every operation
whose `jax.named_scope` is under `xing4/mhc` — the coefficients (the norm over
a token's 14 336 values, phi's matmul, the gates), the Sinkhorn rounds, the
read-in of the mixed stream and the write-back with the stream-to-stream mix,
of all ten sub-blocks; forward, the backward's recomputation and backward —
per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

MHC_SCOPES = ("xing4/mhc", "xing4/mhc/coef", "xing4/mhc/sinkhorn", "xing4/mhc/pre",
              "xing4/mhc/post_res")


def read(run):
    return scope_ms(run, MHC_SCOPES)
