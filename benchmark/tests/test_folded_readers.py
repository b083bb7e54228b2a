"""PR 66's fold: `head_loss_ms`, `optimizer_ms` and `lm_mfu_pct` each read, on
a run with one model's scopes, what the entry they replaced read there. The
old readings are literals: each was the replaced reader's own return on this
very dictionary, taken before its file was deleted (the replaced files are in
git at PR 65)."""

import json
import os

import pytest

from benchmark import common

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _run(scope_s, busy_s=0.0, flops=None, steps=2):
    return {"trace": {"steps": steps, "busy_s": busy_s, "window_s": busy_s + 0.01,
                      "scope_s": dict(scope_s, unattributed=0.003)},
            "window": {"batch": 1, "chips": 1, "step_ms": 1e3 * busy_s / steps},
            "shape": {"model_flops_per_sample": flops} if flops else {},
            "peaks": PEAKS}


RUNS = {
    "olmoe": _run({"olmoe/head_loss": 0.0847, "olmoe/moe/experts": 0.05, "olmoe": 0.001,
                   "optimizer": 0.0409}),
    "glm": _run({"glm4_moe_lite/head_loss": 0.03286, "glm4_moe_lite/mtp/head_loss": 0.0264,
                 "glm4_moe_lite/mtp": 0.011, "glm4_moe_lite/embed": 0.0052, "optimizer": 0.04536}),
    "trinity": _run({"afmoe/head_loss": 0.09134, "afmoe/embed": 0.004, "optimizer": 0.04664}),
    "xing": _run({"xing4/head_loss": 0.0201, "xing4/embed": 0.0017, "optimizer": 0.0437}),
    "ouro": _run({"ouro/exit_loss": 0.0301, "ouro/exit": 0.0077, "ouro/embed": 0.0021,
                  "optimizer": 0.0423}),
    "kimi": _run({"kimi_linear/head_loss": 0.0852, "kimi_linear/embed": 0.0031,
                  "optimizer": 0.03538}),
    "phi": _run({"phi4flash/head_loss": 0.04812, "phi4flash/embed": 0.0208, "phi4flash/norm": 0.003,
                 "optimizer": 0.05134}, busy_s=0.8436, flops=37.53e12),
    "lfm2": _run({"lfm2/head_loss": 0.135084, "lfm2/embed": 0.0049, "optimizer": 0.036332},
                 busy_s=1.6334, flops=52.4e12),
    "qwen": _run({"qwen3_next/head_loss": 0.0731, "qwen3_next/embed": 0.0044, "optimizer": 0.0391},
                 busy_s=1.1353, flops=26.2e12),
}

FOLDED = [
    # (folded reader, the entry it replaced, that entry's cell, the old reading)
    ("head_loss_ms", "lm_head_ms", "olmoe", 42.35),
    ("head_loss_ms", "head_loss_ms", "glm", 29.63),
    ("head_loss_ms", "afmoe_head_loss_ms", "trinity", 45.67),
    ("head_loss_ms", "xing_head_loss_ms", "xing", 10.05),
    ("head_loss_ms", "kda_head_loss_ms", "kimi", 42.6),
    ("head_loss_ms", "sambay_head_loss_ms", "phi", 34.46000000000001),
    ("head_loss_ms", "lfm2_head_loss_ms", "lfm2", 67.542),
    ("optimizer_ms", "optimizer_ms", "olmoe", 20.45),
    ("optimizer_ms", "xing_optimizer_ms", "xing", 21.85),
    ("optimizer_ms", "ut_optimizer_ms", "ouro", 21.15),
    ("optimizer_ms", "kda_optimizer_ms", "kimi", 17.69),
    ("optimizer_ms", "sambay_optimizer_ms", "phi", 25.669999999999998),
    ("optimizer_ms", "lfm2_optimizer_ms", "lfm2", 18.166),
    ("lm_mfu_pct", "sambay_mfu_pct", "phi", 45.16538980872403),
    ("lm_mfu_pct", "lfm2_mfu_pct", "lfm2", 32.56885609351488),
    ("lm_mfu_pct", "gdn_mfu_pct", "qwen", 23.4290361768463),
]


@pytest.mark.parametrize("folded,replaced,model,old_reading", FOLDED)
def test_the_folded_reader_returns_what_the_replaced_reader_returned(
        folded, replaced, model, old_reading):
    assert common.load_module("layer_metrics", folded).read(RUNS[model]) == old_reading
    gone = os.path.join(common.BENCH_DIR, "layer_metrics", replaced + ".py")
    assert replaced == folded or not os.path.exists(gone)


def test_head_loss_is_the_last_part_of_a_scope_and_nothing_else():
    read = common.load_module("layer_metrics", "head_loss_ms").read
    assert read(RUNS["ouro"]) is None                       # exits, no `head_loss`
    assert read(_run({"phi4flash/embed": 0.02})) is None    # the exception alone is no head
    assert read(_run({"m/head_loss_aux": 0.02, "m/head_loss/x": 0.03})) is None
    assert read({"trace": None}) is None and read({}) is None
    three = _run({"m/head_loss": 0.01, "m/mtp/head_loss": 0.02, "m/b/c/head_loss": 0.04})
    assert read(three) == pytest.approx(35.0)


def test_lm_mfu_reads_nothing_without_a_count_a_peak_or_a_traced_step():
    read = common.load_module("layer_metrics", "lm_mfu_pct").read
    whole = RUNS["phi"]
    assert 0 < read(whole) < 100
    for key in ("shape", "peaks", "trace", "window"):
        assert read({**whole, key: None}) is None
    assert read({**whole, "trace": {**whole["trace"], "steps": None}}) is None
    assert read({**whole, "trace": {**whole["trace"], "busy_s": 0.0}}) is None
    assert read({**whole, "shape": {}}) is None


@pytest.mark.parametrize("name", ["head_loss_ms", "optimizer_ms", "lm_mfu_pct"])
def test_a_folded_entry_lists_its_cells(name):
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    cells = [w["name"] for w in bench["workloads"]]
    assert entry["workloads"] and set(entry["workloads"]) <= set(cells)
    assert entry["workloads"] == [c for c in cells if c in entry["workloads"]]   # the cells' order
    assert not any("criteo" in c for c in entry["workloads"])
    assert entry["moves"] == "samples_per_s_per_chip" and entry["source"] == "device_trace"
