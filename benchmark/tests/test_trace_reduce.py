"""The trace reduction on a small trace in the recorded format
(fixtures/small.xplane.pb, written by fixtures/make_trace_fixture.py, whose
docstring has the hand count)."""

import os

import pytest

from benchmark import trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "small.xplane.pb")
US = 1e-6


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
