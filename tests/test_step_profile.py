"""Step profiler (observability/profile.py): phase accumulation and
windows, gauge export, prefetcher data_wait/h2d attribution, the cohort's
follower-stats exchange codec, and the health scorer surfacing the WHY
(phase breakdown) on straggler infos."""

import time

import numpy as np
import pytest

from elasticdl_tpu.observability import profile
from elasticdl_tpu.observability.profile import StepProfiler, timed_iter
from elasticdl_tpu.observability.registry import default_registry


@pytest.fixture(autouse=True)
def _fresh_profiler():
    profile.reset_for_tests()
    yield
    profile.reset_for_tests()


def test_phases_accumulate_and_normalize_per_step():
    prof = StepProfiler(window=8)
    prof.add("data_wait", 0.010)
    prof.add("compute", 0.030)
    prof.add("compute", 0.010)   # same step, accumulates
    prof.step_done()
    snap = prof.snapshot(update_memory=False)
    assert snap["phase_data_wait_ms"] == 10.0
    assert snap["phase_compute_ms"] == 40.0
    assert snap["profiled_steps"] == 1
    # a grouped dispatch normalizes to per-step values
    prof.add("compute", 0.080)
    prof.step_done(steps=4)      # 20ms per step
    snap = prof.snapshot(update_memory=False)
    assert snap["profiled_steps"] == 5
    assert snap["phase_compute_ms"] == pytest.approx(30.0)  # (40+20)/2


def test_window_is_bounded_with_maintained_sums():
    prof = StepProfiler(window=4)
    for i in range(10):
        prof.add("compute", 0.001 * (i + 1))
        prof.step_done()
    snap = prof.snapshot(update_memory=False)
    # only the last 4 steps (7,8,9,10 ms) contribute
    assert snap["phase_compute_ms"] == pytest.approx(8.5)


def test_phase_context_manager_and_unknown_phase_dropped():
    prof = StepProfiler(window=4)
    with prof.phase("data_wait"):
        time.sleep(0.005)
    prof.add("weird_phase", 1.0)
    prof.step_done()
    snap = prof.snapshot(update_memory=False)
    assert snap["phase_data_wait_ms"] >= 4.0
    assert not any("weird" in k for k in snap)


def test_gauges_exported_per_phase():
    prof = StepProfiler(window=4)
    prof.add("compute", 0.020)
    prof.step_done()
    g = default_registry().get("edl_step_phase_seconds")
    assert g is not None
    assert g.value(phase="compute") == pytest.approx(0.020)


def test_memory_watermarks_best_effort():
    prof = StepProfiler()
    prof.update_memory()
    snap = prof.snapshot()
    # host RSS exists on linux; device side is 0 without a jax backend
    assert snap.get("mem_host_mb", 0) > 0
    g = default_registry().get("edl_mem_host_rss_mb")
    assert g is not None and g.value() > 0


def test_timed_iter_attributes_pulls():
    prof = StepProfiler(window=4)

    def slow_source():
        for i in range(3):
            time.sleep(0.004)
            yield i

    assert list(timed_iter(slow_source(), prof)) == [0, 1, 2]
    prof.step_done()
    snap = prof.snapshot(update_memory=False)
    assert snap["phase_data_wait_ms"] >= 10.0


def test_prefetcher_attributes_data_wait_and_h2d(mesh8):
    from elasticdl_tpu.data.prefetch import prefetch_to_device

    def batches():
        for i in range(4):
            time.sleep(0.003)
            yield {
                "features": np.full((8, 3), i, np.float32),
                "mask": np.ones((8,), np.float32),
            }

    out = list(prefetch_to_device(mesh8, batches(), depth=2))
    assert len(out) == 4
    prof = profile.get_profiler()
    prof.step_done()
    snap = prof.snapshot(update_memory=False)
    # four source pulls at >=3ms each
    assert snap["phase_data_wait_ms"] >= 10.0
    # the device_put dispatch is nonzero too
    assert snap.get("phase_h2d_ms", 0) > 0


# ---------------------------------------------------------------------- #
# cohort follower-stats exchange (satellite: the follower->leader channel)


def test_allgather_ints_single_process_shape():
    from elasticdl_tpu.parallel.elastic import CohortContext

    ctx = CohortContext("localhost:1", num_processes=1, process_id=0)
    out = ctx.allgather_ints([1, 2, 3, 2**40])
    assert out.shape == (1, 4)
    assert out[0].tolist() == [1, 2, 3, 2**40]   # full 64-bit fidelity


def _cohort(num_processes=3):
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.parallel.elastic import CohortContext
    from elasticdl_tpu.worker.cohort import CohortWorker

    cfg = JobConfig(model_def="mnist.mnist_cnn.custom_model",
                    num_processes=num_processes)
    ctx = CohortContext("localhost:1", num_processes=num_processes,
                        process_id=0)
    return CohortWorker(cfg, ctx=ctx)


def test_exchange_row_roundtrip():
    w = _cohort()
    w._step_stats.observe_step(0.025, 64)
    w._step_stats.observe_step(0.035, 64)
    profile.get_profiler().add("data_wait", 0.012)
    profile.get_profiler().add("compute", 0.030)
    profile.get_profiler().step_done()
    row = w._exchange_row()
    decoded = w._decode_exchange_row(row)
    assert decoded["steps"] == 2
    assert decoded["step_p50_ms"] == pytest.approx(30.0, abs=0.01)
    assert decoded["phase_data_wait_ms"] == pytest.approx(12.0, abs=0.01)
    assert decoded["phase_compute_ms"] == pytest.approx(30.0, abs=0.01)


def test_member_beats_prefer_follower_local_rows():
    from elasticdl_tpu.observability.health import decode_stats

    w = _cohort()
    w._member_ids = [7, 8]
    w._phase = "train"
    w._step_stats.observe_step(0.010, 64)   # the leader's own cadence
    # follower p1 exchanged a row; p2 has not yet (just re-formed)
    w._member_stats = {1: {"steps": 5, "step_p50_ms": 42.0,
                           "phase_data_wait_ms": 33.0}}
    beats = w._member_beats()
    assert [b.worker_id for b in beats] == [7, 8]
    s1 = decode_stats(beats[0].stats_json)
    s2 = decode_stats(beats[1].stats_json)
    assert s1["source"] == "follower-local"
    assert s1["step_p50_ms"] == 42.0 and s1["phase_data_wait_ms"] == 33.0
    assert s1["process_index"] == 1 and s1["phase"] == "train"
    assert s2["source"] == "leader-coalesced"
    assert s2["step_p50_ms"] == 10.0   # falls back to the leader's window


def test_exchange_member_stats_single_process_noop():
    w = _cohort(num_processes=1)
    w._exchange_member_stats()         # must not touch collectives
    assert w._member_stats == {}


# ---------------------------------------------------------------------- #
# the scorer surfaces WHY (straggler info carries the phase breakdown)


def test_straggler_info_carries_phase_breakdown():
    from elasticdl_tpu.master.membership import Membership
    from elasticdl_tpu.observability.health import ClusterHealth

    membership = Membership(heartbeat_timeout_s=1e9)
    ids = [membership.register(f"w{i}").worker_id for i in range(4)]
    for wid in ids[:3]:
        membership.heartbeat(wid, stats={"step_p50_ms": 10.0})
    membership.heartbeat(ids[3], stats={
        "step_p50_ms": 500.0, "phase": "train",
        "phase_data_wait_ms": 480.0, "phase_compute_ms": 15.0,
        "mem_host_mb": 1234.5,
    })
    health = ClusterHealth(membership)
    snap = health.update()
    assert snap["straggler_count"] == 1
    info = snap["stragglers"][0]
    assert info["worker_id"] == ids[3]
    # the WHY: blocked on the input pipeline, not compute-bound
    assert info["phase_data_wait_ms"] == 480.0
    assert info["phase_compute_ms"] == 15.0
    assert info["mem_host_mb"] == 1234.5


def test_step_phase_gauges_appear_in_live_scrape():
    """ISSUE 9 acceptance: edl_step_phase_seconds / edl_mem_* gauges show
    up in a LIVE /metrics scrape once a step has been profiled."""
    import urllib.request

    from elasticdl_tpu.observability.http import ObservabilityServer

    prof = profile.get_profiler()
    prof.add("compute", 0.015)
    prof.add("data_wait", 0.002)
    prof.step_done()
    prof.update_memory()
    server = ObservabilityServer(role="worker-0")
    port = server.start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as resp:
            text = resp.read().decode()
    finally:
        server.stop()
    assert 'edl_step_phase_seconds{phase="compute"}' in text
    assert 'edl_step_phase_seconds{phase="data_wait"}' in text
    assert "edl_mem_host_rss_mb" in text


def test_a_compile_span_reaches_the_profiler_s_trace_through_the_bridge(tmp_path):
    """`Trainer` opens `tracing.span("compile")` where it had its own
    annotation: the bridge in `tracing.span` makes it `edl.compile` in a
    device trace, with the span's attributes — for an AOT compilation and
    for a first dispatch alike — and `start.state` beside it."""
    import jax

    from tests.test_edl_annotations import _spans_of
    from tests.test_startup_ledger import _stack, _tiny_trainer

    trainer, spec, mesh = _tiny_trainer()
    example, stack = _stack(mesh, spec, 2)
    with jax.profiler.trace(str(tmp_path)):
        state = trainer.init_state(example)
        trainer.aot_compile_train_step(state, example)
        trainer.train_many(state, stack)
    spans = _spans_of(tmp_path)
    assert [s.stats for s in spans if s.name == "edl.compile"] == [
        {"program": "train_step", "aot": 1},
        {"program": "train_many", "aot": 0}]
    assert [s.stats for s in spans if s.name == "edl.start.state"] == [
        {"model": "deepfm.deepfm"}]
