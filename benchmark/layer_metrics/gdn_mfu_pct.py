"""layer: trainer. The step's MODEL FLOPs (`model_flops_per_sample` of the
configuration's shape functions times the sequences a step: forward +
backward, NOTHING recomputed counted, attention over visible pairs only, the
held experts' matmuls for the pairs the run counted, the delta rule by the
scalar form's arithmetic, the convolution and the gates — no matmul — not at
all) over the chip's peak bf16 FLOP/s, over the TRACED step's device time
(device 0's busy seconds over the traced steps): the whole step's share of the
peak, which a later claim in this cell is bounded by. `lfm2_mfu_pct`'s form,
bound to the Qwen3-Next cell; it reads nothing where the program has no
`qwen3_next` scope."""

from benchmark import common

traced_gdn = common.load_module("layer_metrics", "gdn_flash_roofline").traced_gdn


def read(run):
    shape, peaks, trace, w = (run.get("shape"), run.get("peaks"), traced_gdn(run),
                              run.get("window"))
    if not shape or not peaks or not trace or not trace.get("busy_s"):
        return None
    flops = shape["model_flops_per_sample"] * w["batch"] / w["chips"]
    return 100.0 * flops / (trace["busy_s"] / trace["steps"]) / peaks["bf16_flops_per_s"]
