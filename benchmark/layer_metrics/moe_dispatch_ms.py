"""layer: sparse experts. Device trace, device 0: router + dispatch (sort,
gather into expert order) + combine (gather back, weighted sum over the
slots), forward and backward, per traced step: what a dense feed-forward of
the same arithmetic would not pay."""

from benchmark import common

_moe_ms = common.load_module("layer_metrics", "moe_ms")


def read(run):
    return _moe_ms.scope_ms(run, (
        "olmoe/moe/router", "olmoe/moe/dispatch", "olmoe/moe/combine"))
