"""BENCHMARK.json against the contract's character rules, and against the
files its names must resolve to."""

import json
import os
import re

import pytest

from benchmark import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units():
    bench = _bench()
    names = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[key]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((key in ("end_to_end", "per_layer"), entry["name"]))
    assert len(set(names)) == len(names)
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)
    assert len(bench["per_layer"]) <= 128                      # the contract's limit


def test_every_entry_has_a_reader_and_every_reader_an_entry():
    """A per-layer metric is named for its LAYER, not for a model (PR 66 folded
    the seven head-and-loss, six optimizer and three traced-MFU entries into
    one reader each): a reader without an entry is a leftover, an entry
    without a reader a run that cannot start."""
    bench = _bench()
    for kind, key in (("layer_metrics", "per_layer"), ("end_to_end", "end_to_end")):
        files = {f[:-3] for f in os.listdir(os.path.join(common.BENCH_DIR, kind))
                 if f.endswith(".py") and f != "__init__.py"}
        assert files == {m["name"] for m in bench[key]}, kind
    for gone in ("lm_head_ms", "afmoe_head_loss_ms", "xing_head_loss_ms", "kda_head_loss_ms",
                 "sambay_head_loss_ms", "lfm2_head_loss_ms", "xing_optimizer_ms", "ut_optimizer_ms",
                 "kda_optimizer_ms", "sambay_optimizer_ms", "lfm2_optimizer_ms", "sambay_mfu_pct",
                 "lfm2_mfu_pct", "gdn_mfu_pct", "host_wait_pct", "input_host_samples_per_s"):
        assert all(m["name"] != gone for m in bench["per_layer"]), gone


@pytest.mark.parametrize("name", ["head_loss_ms", "optimizer_ms", "lm_mfu_pct", "mfu_pct"])
def test_a_metric_read_in_some_cells_alone_says_which(name):
    """A reader that finds its subject in any model's run is bound by its
    `workloads` list and by nothing else: without one it would print in every
    cell that reports `samples_per_s_per_chip`, the Criteo cells too."""
    entry = next(m for m in _bench()["per_layer"] if m["name"] == name)
    assert entry.get("workloads")


def test_every_name_resolves_to_files():
    bench = _bench()
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        resolved = common.resolve_cell(w["name"])
        common.load_module("drivers", resolved["traffic"]["driver"])
        common.load_json("cardinalities", resolved["config"]["cardinalities"] + ".json")
        for kind in ("reference", "flops"):
            path = os.path.join(common.BENCH_DIR, kind, common.model_name(resolved["config"]) + ".py")
            assert os.path.exists(path), path
        assert resolved["config"]["chips"] == w["chips"]
        assert len(resolved["end_to_end"]) >= 2 and resolved["per_layer"]
    for m in bench["end_to_end"]:
        assert os.path.exists(os.path.join(common.BENCH_DIR, "end_to_end", m["name"] + ".py"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(common.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for c in bench["configs"]:
        with open(os.path.join(common.ROOT, c["file"])) as f:
            config = json.load(f)
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert config["source"] and config["deployment"]
