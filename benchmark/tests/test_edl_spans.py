"""The worker loop's spans against the device's idle gaps, on a small trace
in the recorded format (fixtures/edl.xplane.pb, written by
fixtures/make_edl_trace_fixture.py, whose docstring has the hand count), and
the six metrics that read the split."""

import json
import os
import shutil

import pytest

from benchmark import common, edl_spans

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "edl.xplane.pb")
SMALL = os.path.join(os.path.dirname(__file__), "fixtures", "small.xplane.pb")
US = 1e3            # ns
METRICS = ("idle_named_pct", "gap_input_ms", "gap_h2d_ms", "gap_step_ms",
           "gap_turn_ms", "gap_loop_ms")


@pytest.fixture(scope="module")
def figures():
    return edl_spans.figures_of(FIXTURE)


def test_innermost_span_takes_the_instant():
    spans = [(0, 100, "edl.task_turn"), (10, 90, "edl.task"),
             (20, 30, "edl.h2d"), (30, 95, "edl.compute")]   # outlasts edl.task: cut to it
    segments = edl_spans.innermost_segments(spans)
    assert [(s, e, path[-1]) for s, e, path in segments] == [
        (0, 10, "edl.task_turn"), (10, 20, "edl.task"), (20, 30, "edl.h2d"),
        (30, 90, "edl.compute"), (90, 100, "edl.task_turn")]
    assert segments[2][2] == ("edl.task_turn", "edl.task", "edl.h2d")
    # where trace_reduce.attribute_gaps would give both gaps whole to the turn
    split = edl_spans.split_gaps([(5, 25), (85, 120)], segments)
    assert split["by_span"] == {"edl.task_turn": 15.0, "edl.task": 10.0,
                                "edl.h2d": 5.0, "edl.compute": 5.0, None: 20.0}


def test_a_span_without_a_bucket_takes_the_one_around_it():
    assert edl_spans.bucket_of(("edl.task_turn", "edl.report", "edl.ckpt.save")) == "turn"
    assert edl_spans.bucket_of(("edl.task_turn", "edl.task", "edl.compute", "edl.h2d")) == "h2d"
    assert edl_spans.bucket_of(("edl.task_turn", "edl.task", "edl.compute", "edl.compile")) == "loop"
    assert edl_spans.bucket_of(("edl.rescale.mesh",)) == "loop"


def test_the_split_of_the_fixture(figures):
    assert figures["window_ns"] == (pytest.approx(5e6 + 150 * US), pytest.approx(5e6 + 450 * US))
    assert figures["dispatches"] == 2
    assert figures["idle_ns"] == pytest.approx(80 * US)
    assert figures["named_ns"] == pytest.approx(75 * US)
    assert {k: round(v / US) for k, v in figures["by_bucket"].items()} == {
        "input": 3, "h2d": 8, "step": 20, "turn": 25, "loop": 19}
    by_span = {k: round(v / US) for k, v in figures["by_span"].items()}
    assert by_span[None] == 5                           # the gap under no span
    assert by_span["edl.ckpt.save"] == 5          # named; counted with edl.report
    assert "edl.input.make_batch" not in by_span        # neither the pool's nor the loop's
    assert by_span["edl.data_wait"] == 3
    assert figures["spans_in_window"]["edl.compute"] == 2


def test_the_pools_line_is_not_the_task_loops():
    from jax.profiler import ProfileData

    spans = edl_spans.task_loop_spans(ProfileData.from_file(FIXTURE))
    assert sum(1 for s in spans if s[2] == "edl.task_turn") == 2
    assert (5e6 + 240 * US, 5e6 + 300 * US, "edl.input.make_batch") not in spans


@pytest.fixture()
def kept_trace(monkeypatch, tmp_path):
    """A run whose driver kept `trace.xplane.pb` where run.py's `keep` puts it."""
    monkeypatch.setattr(common, "OUT_DIR", str(tmp_path))

    def run_with(path):
        os.makedirs(tmp_path / "deepfm-criteo.job", exist_ok=True)
        shutil.copyfile(path, tmp_path / "deepfm-criteo.job" / "trace.xplane.pb")
        return {"workload": "deepfm-criteo.job", "trace": {"busy_s": 1.0, "window_s": 2.0}}

    return run_with


def test_the_six_readers_and_their_identity(kept_trace):
    run = kept_trace(FIXTURE)
    value = {name: common.load_module("layer_metrics", name).read(run) for name in METRICS}
    assert value["idle_named_pct"] == pytest.approx(93.75)
    assert value["gap_input_ms"] == pytest.approx(3e-3 / 2)
    assert value["gap_h2d_ms"] == pytest.approx(8e-3 / 2)
    assert value["gap_step_ms"] == pytest.approx(20e-3 / 2)
    assert value["gap_turn_ms"] == pytest.approx(25e-3 / 2)
    assert value["gap_loop_ms"] == pytest.approx(19e-3 / 2)
    # the five add up to: idle share x window / dispatches x the named share
    idle_pct, window_ms, dispatches = 100.0 * 80 / 300, 0.3, 2
    assert sum(value[name] for name in METRICS[1:]) == pytest.approx(
        idle_pct / 100.0 * window_ms / dispatches * value["idle_named_pct"] / 100.0)


def test_nothing_to_read_is_none_and_never_raises(kept_trace):
    readers = [common.load_module("layer_metrics", name).read for name in METRICS]
    # an untraced run; a traced run of a program without the spans (the
    # parent of the PR that added them; a resident cell); no kept file
    for run in ({"workload": "deepfm-criteo.job", "trace": None},
                kept_trace(SMALL),
                {"workload": "no-such-cell", "trace": {"busy_s": 1.0, "window_s": 2.0}}):
        assert [read(run) for read in readers] == [None] * len(METRICS)


def test_the_new_metrics_are_the_job_cells_alone():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in METRICS:
        m = per_layer[name]
        assert m["workloads"] == ["deepfm-criteo.job"] and m["source"] == "program_span"
        assert m["moves"] == "samples_per_s_per_chip"
    assert [n for n in per_layer][-len(METRICS):] == list(METRICS)
