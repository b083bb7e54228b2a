"""The trace reduction on a small trace in the recorded format
(fixtures/small.xplane.pb, written by fixtures/make_trace_fixture.py, whose
docstring has the hand count)."""

import os

import pytest

from benchmark import trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "small.xplane.pb")
EDL_FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "edl.xplane.pb")
US = 1e-6
FUSION_302 = ("%fusion.302 = f32[212992,11]{0,1:T(8,128)S(1)} fusion(f32[33800192,11]{0,1:T(8,128)} "
              "%get-tuple-element.2197, s32[212992]{0:T(1024)S(1)} %copy), kind=kCustom")
# what the reduction as it stood at PR 65 made of the two kept traces, to the
# last digit: PR 66 names collectives by opcode, kernels by name and a job's
# idle gaps by the program's spans, and moves none of these
KEPT = {
    FIXTURE: {"window_ns": (5000000.0, 5100000.0), "window_s": 0.0001, "busy_s": 9e-05,
              "gaps_ns": [(5050000.0, 5060000.0)], "mosaic_s": 2e-05, "mosaic_calls": 1,
              "collective_s": 4.5e-05, "collective_exposed_s": 1.5e-05,
              "per_op_s": [3e-05, 2e-05, 1e-05, 3e-05]},
    EDL_FIXTURE: {"window_ns": (5150000.0, 5450000.0), "window_s": 0.0003, "busy_s": 0.00022,
                  "gaps_ns": [(5250000.0, 5310000.0), (5150000.0, 5160000.0),
                              (5440000.0, 5450000.0)], "mosaic_s": 0.0, "mosaic_calls": 0,
                  "collective_s": 0.0, "collective_exposed_s": 0.0, "per_op_s": [0.00022]},
}


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_file(FIXTURE)


def test_busy_and_idle(reduced):
    device = reduced["devices"][0]
    assert device["window_s"] == pytest.approx(100 * US)
    assert device["busy_s"] == pytest.approx(90 * US)       # the while is no leaf
    assert [round((e - s) / 1e3) for s, e in device["gaps_ns"]] == [10]


def test_a_mosaic_calls_time(reduced):
    device = reduced["devices"][0]
    assert device["mosaic_calls"] == 1
    assert device["mosaic_s"] == pytest.approx(20 * US)


def test_a_collectives_exposed_part(reduced):
    device = reduced["devices"][0]
    # [20,55] in flight on the async line and the all-reduce [60,70] on the
    # operations' line; beside no other operation: [50,55] and [60,70]
    assert device["collective_s"] == pytest.approx(45 * US)
    assert device["collective_exposed_s"] == pytest.approx(15 * US)


def test_own_time_and_breakdown(reduced):
    summary = trace_reduce.summary(reduced)
    ops = dict(summary["device_ops"])
    assert "while.42 (while)" not in ops
    assert ops["fusion.302 (fusion)"] == pytest.approx(30 * US)
    assert ops["place_sorted_grads.4 (custom-call)"] == pytest.approx(20 * US)
    assert ops["multiply_add_fusion.32 (fusion)"] == pytest.approx(30 * US)
    assert summary["idle_gaps"] == [["bench.readback", pytest.approx(10 * US)]]
    assert summary["busy_s"] / summary["window_s"] == pytest.approx(0.9)


def test_names():
    assert trace_reduce.op_kind("%all-gather-start.2 = (f32[8]) all-gather-start(...)") == "all-gather-start"
    assert trace_reduce.is_collective("%all-reduce.7 = f32[4] all-reduce(...)")
    assert trace_reduce.is_collective("%all-gather-start.2 = (f32[8]) all-gather-start(...)")
    assert not trace_reduce.is_collective("%fusion.3 = f32[4] fusion(%all-reduce.7)")
    assert trace_reduce.short_name("%fusion.3 = f32[4]{0} fusion(f32[4] %x), kind=kLoop") == "fusion.3 (fusion)"


@pytest.mark.parametrize("path", sorted(KEPT))
def test_the_reduction_of_a_kept_trace_is_what_it_was(path):
    device = trace_reduce.reduce_file(path)["devices"][0]
    want = dict(KEPT[path])
    assert list(device["per_op_s"].values()) == want.pop("per_op_s")
    assert {k: device[k] for k in want} == want


def test_seconds_by_kernel_counts_the_named_kernel_alone():
    call = 'custom-call(f32[8]{0} %x), custom_call_target="tpu_custom_call"'
    per_op_s = {f"%place_sorted_grads.4 = f32[11,8]{{1,0}} {call}": 0.003,
                f"%cin_bwd.28 = f32[8]{{0}} {call}": 0.034,
                f"%cin_bwd.27 = f32[8]{{0}} {call}": 0.034,
                f"%ssd_pass2.1 = f32[8]{{0}} {call}": 0.5,
                "%custom-call.9 = f32[8]{0} custom-call(f32[8]{0} %x), custom_call_target=\"Sort\"": 0.2,
                FUSION_302: 0.25}
    by_kernel = trace_reduce.seconds_by_kernel(per_op_s)
    assert by_kernel == {"place_sorted_grads": 0.003, "cin_bwd": 0.068, "ssd_pass2": 0.5}
    summary = trace_reduce.summary(trace_reduce.reduce_file(FIXTURE))
    assert summary["mosaic_kernel_s"] == {"place_sorted_grads": pytest.approx(20 * US)}
    assert summary["mosaic_s"] == pytest.approx(20 * US)        # stays beside it


@pytest.mark.parametrize("name,collective", [
    # JAX names the instruction for its primitive; the opcode says what it is
    ("%all_to_all.10 = f32[4,8192,11]{2,1,0} all-to-all(f32[4,8192,11]{2,1,0} %x), "
     "replica_groups={{0,1,2,3}}, dimensions={0}", True),
    ("%pmax.3 = s32[]{:T(128)} all-reduce(s32[] %x), replica_groups={{0,1,2,3}}, "
     "to_apply=%max", True),
    ("%all-gather-start.2 = (f32[8]{0}, f32[32]{0}) all-gather-start(f32[8]{0} %g), "
     "dimensions={0}", True),
    ("%psum.5 = (f32[8]{0}, f32[4]{0}) all-reduce(f32[8]{0} %a, f32[4]{0} %b), "
     "to_apply=%add", True),
    ("all-reduce.7", True),                 # a bare name: its stem is all there is
    ("%fusion.3 = f32[4]{0} fusion(f32[4]{0} %all-reduce.7), kind=kLoop", False),
    ("%all_to_all_fusion.2 = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop", False),
    ("%all-reduce-scatter.3 = f32[4]{0} fusion(f32[16]{0} %x), kind=kCustom", False),
    ("all_to_all.10", False)])
def test_a_collective_is_told_by_its_opcode(name, collective):
    assert trace_reduce.is_collective(name) is collective


def test_an_idle_gap_is_split_among_the_innermost_spans_over_it():
    turn, task = ("edl.task_turn",), ("edl.task_turn", "edl.task")
    pieces = [(0, 10, turn), (10, 20, task), (20, 30, task + ("edl.h2d",)),
              (30, 90, task + ("edl.compute", "edl.compute.readback")), (90, 100, turn)]
    gaps = [(5, 25), (22, 29), (28, 60), (85, 120), (200, 210)]
    named = dict(trace_reduce.split_gaps(gaps, pieces))
    # [5,25]: turn 5, task 10, h2d 5; [28,60]: h2d 2, read-back 30; [85,120]:
    # read-back 5, turn 10, nothing 20; [200,210] lies under nothing
    assert named == {"edl.task_turn": pytest.approx(15e-9), "edl.task": pytest.approx(10e-9),
                     "edl.h2d": pytest.approx(14e-9),
                     "edl.compute.readback": pytest.approx(35e-9),
                     "unattributed": pytest.approx(30e-9)}
    assert sum(named.values()) == pytest.approx(sum(e - s for s, e in gaps) / 1e9)
    assert [k for k, _ in trace_reduce.split_gaps(gaps, pieces, top=2)] == [
        "edl.compute.readback", "unattributed"]
    # `attribute_gaps` over the spans themselves gives the turn, which covers
    # everything, every gap it touches
    spans = [(0, 100, "edl.task_turn"), (10, 90, "edl.task"), (20, 30, "edl.h2d"),
             (30, 90, "edl.compute.readback")]
    assert [k for k, _ in trace_reduce.attribute_gaps(gaps[:4], spans)] == ["edl.task_turn"]


def test_a_job_s_gaps_are_named_by_the_program_s_spans_and_nothing_else_moves():
    reduced = trace_reduce.reduce_file(EDL_FIXTURE)
    assert reduced["annotations"] == [] and reduced["program_spans"]
    assert all(name.startswith("edl.") for _, _, path in reduced["program_spans"] for name in path)
    ends = [(s, e) for s, e, _ in reduced["program_spans"]]
    assert ends == sorted(ends) and all(a[1] <= b[0] for a, b in zip(ends, ends[1:]))
    summary = trace_reduce.summary(reduced)
    whole = trace_reduce.split_gaps(reduced["devices"][0]["gaps_ns"], reduced["program_spans"],
                                    top=99)
    assert summary["idle_gaps"] == whole[:10] and len(whole) == 11
    gaps = dict(whole)
    # 80 us idle in the window, 5 of them under no span: the split the `gap_*`
    # metrics read (`edl_spans.split_gaps`), name for name
    from benchmark import edl_spans
    by_span = edl_spans.figures_of(EDL_FIXTURE)["by_span"]
    want = {("unattributed" if k is None else k): pytest.approx(v / 1e9)
            for k, v in by_span.items() if v > 0}
    assert gaps == want
    assert gaps["unattributed"] == pytest.approx(5 * US)
    assert sum(gaps.values()) == pytest.approx(80 * US)
    assert "edl.input.make_batch" not in gaps           # the parse pool's thread takes no gap
    assert (summary["window_s"], summary["busy_s"]) == (0.0003, 0.00022)
    # a resident cell annotates its own window: the program's spans are not asked
    assert trace_reduce.reduce_file(FIXTURE)["program_spans"] == []
