"""layer: sparse attention. Device trace, device 0: time under
`keye/attn/index_loss` (the target p-hat — every head's q·kT over the kept keys
once more, summed over the heads — the indexer's softmax over the kept keys,
the KL sum and its gradient with respect to the scores — not the blocks of
the score plane themselves and their pull-back, which `dsa_index_ms` reads),
per traced step."""

from benchmark import common

scope_ms = common.load_module("layer_metrics", "moe_ms").scope_ms


def read(run):
    return scope_ms(run, ("keye/attn/index_loss",))
