"""layer: hyper-connections. Device trace, device 0: time under
`xing4/mhc/pre` (h = sum_i H_pre,i X_i) and `xing4/mhc/post_res` (X'_i =
sum_j H_res,ij X_j + H_post,i y): the two passes over the four-stream state a
sub-block makes, ten sub-blocks, forward, recomputation and backward, per
traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms

MIX_SCOPES = ("xing4/mhc/pre", "xing4/mhc/post_res")


def read(run):
    return scope_ms(run, MIX_SCOPES)
