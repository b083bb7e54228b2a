"""layer: trainer. The step's MODEL FLOPs (`model_flops_per_sample` of the
configuration's shape functions times the sequences a step: forward +
backward, NOTHING recomputed counted, attention over visible pairs only, the
held experts' matmuls for the pairs the run counted, the gates and the taps —
no matmul — not at all) over the chip's peak bf16 FLOP/s, over the TRACED
step's device time (device 0's busy seconds over the traced steps): the whole
step's share of the peak, which a later claim in this cell is bounded by.
`sambay_mfu_pct`'s form, bound to the LFM2 cell; it reads nothing where the
program has no `lfm2` scope."""


from benchmark import common

traced_lfm2 = common.load_module("layer_metrics", "lfm2_flash_ms").traced_lfm2


def read(run):
    shape, peaks, trace, w = (run.get("shape"), run.get("peaks"), traced_lfm2(run),
                              run.get("window"))
    if not shape or not peaks or not trace or not trace.get("busy_s"):
        return None
    flops = shape["model_flops_per_sample"] * w["batch"] / w["chips"]
    return 100.0 * flops / (trace["busy_s"] / trace["steps"]) / peaks["bf16_flops_per_s"]
