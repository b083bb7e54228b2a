"""layer: head and loss. Device trace, device 0: time under every scope whose
LAST part is `head_loss` (the final norm, the vocabulary-wide head matmul, the
float32 cross entropy, and their backward; GLM's two logit streams are
`glm4_moe_lite/head_loss` and `glm4_moe_lite/mtp/head_loss`, one head matrix
used twice), per traced step. One reader for every model: the metric is named
for the layer, and `BENCHMARK.json` lists the cells that print it.

One written exception: Phi-4-mini-flash's head IS its embedding, so the
gather and, backward, the scatter-add into that same matrix
(`phi4flash/embed`) count with it, as they have since PR 59."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms
TIED_EMBEDDING = ("phi4flash/embed",)


def read(run):
    scope_s = (run.get("trace") or {}).get("scope_s") or {}
    heads = tuple(sorted(s for s in scope_s if s.rsplit("/", 1)[-1] == "head_loss"))
    if not heads:
        return None
    return scope_ms(run, heads + TIED_EMBEDDING)
