"""layer: trainer. The step's MODEL FLOPs (`model_flops_per_sample` of the
configuration's shape functions times the sequences a step: forward +
backward, NOTHING recomputed counted, attention over visible pairs only, the
held experts' matmuls for the pairs the run counted, what is no matmul — a
scan, a gate, a tap — not at all) over the chip's peak bf16 FLOP/s, over the
TRACED step's device time (device 0's busy seconds over the traced steps):
the whole step's share of the peak, which a later claim in a cell is bounded
by. `mfu_pct`'s count over the device's clock instead of the host's. One
reader for every language-model cell: `BENCHMARK.json` lists those whose
count a chip run has shown to stay under 100."""


def read(run):
    shape, peaks, trace, w = (run.get("shape"), run.get("peaks"), run.get("trace"),
                              run.get("window"))
    if not (shape and peaks and trace and w) or not shape.get("model_flops_per_sample") \
            or not trace.get("steps") or not trace.get("busy_s"):
        return None
    flops = shape["model_flops_per_sample"] * w["batch"] / w["chips"]
    return 100.0 * flops / (trace["busy_s"] / trace["steps"]) / peaks["bf16_flops_per_s"]
