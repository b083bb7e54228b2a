"""layer: head and loss. Device trace, device 0: time under
`glm4_moe_lite/head_loss` and `glm4_moe_lite/mtp/head_loss` (each stream's
final norm, the vocabulary-wide head matmul, the float32 cross entropy, and
their backward: one head matrix, used twice), per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("glm4_moe_lite/head_loss", "glm4_moe_lite/mtp/head_loss"))
