"""layer: head and loss. Device trace, device 0: time under `ouro/exit` (the
exit gate, the 49 152-wide head matmul and the float32 cross entropy of each
of the four exits, one exit's logits at a time, forward and backward) and
`ouro/exit_loss` (the exit distribution, its entropy and the expected loss),
per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("ouro/exit", "ouro/exit_loss"))
