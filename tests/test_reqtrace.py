"""Request diaries (observability/reqtrace.py, ISSUE 19): the
per-stage attribution invariant (stages sum to the call's wall) on the
slow / error / degraded / hedge-win shapes, tail-based sampling (fast
calls drop at O(1), the tail retains), the bounded retained ring,
replay-identical diaries in flight bundles, the incident CLI's
slow_calls section and its strict sum-to-wall check, the heartbeat
payload, and the master-side dominant-stage-shift fleet series."""

import json

import pytest

from elasticdl_tpu.observability import flight, reqtrace
from elasticdl_tpu.observability.reqtrace import (
    BUNDLE_SLOW_CALLS,
    STAGES,
    DiaryRecorder,
    FleetAttribution,
)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(autouse=True)
def _fresh():
    reqtrace.reset_for_tests()
    flight.reset_for_tests()
    yield
    reqtrace.reset_for_tests()
    flight.reset_for_tests()


def _sum_to_wall(rec_dict, tol=0.01):
    wall = rec_dict["wall_s"]
    total = sum(rec_dict["stages"].values())
    return abs(total - wall) <= max(tol * wall, 1e-9)


def _arm(rec, clk, op="pull", n=40, wall=0.001):
    """Push the op past WARMUP with fast calls so the p99 threshold is
    armed (and equal to `wall` — every sample identical)."""
    for _ in range(n):
        d = rec.start(op)
        clk.advance(wall)
        assert rec.finish(d) is False       # fast: dropped
    assert rec.threshold_s(op) is not None


# ------------------------------------------------------------------ #
# attribution invariant, per finish shape


def test_slow_path_stages_sum_to_wall():
    clk = FakeClock()
    rec = DiaryRecorder(clock=clk)
    _arm(rec, clk)
    d = rec.start("pull", owner=0)
    with reqtrace.stage("wire", clock=clk):
        clk.advance(0.030)
    clk.advance(0.002)                      # unattributed -> `other`
    assert rec.finish(d) is True            # beyond the armed p99
    (entry,) = rec.retained()
    assert entry["status"] == "ok" and entry["op"] == "pull"
    assert entry["stages"]["wire"] == pytest.approx(0.030, abs=1e-9)
    assert entry["stages"]["other"] == pytest.approx(0.002, abs=1e-9)
    assert _sum_to_wall(entry)
    assert entry["known_share"] == pytest.approx(0.030 / 0.032, abs=1e-4)


def test_error_path_retains_and_sums_before_warmup():
    clk = FakeClock()
    rec = DiaryRecorder(clock=clk)
    d = rec.start("pull")
    with reqtrace.stage("budget_wait", clock=clk):
        clk.advance(0.005)
    with reqtrace.stage("wire", clock=clk):
        clk.advance(0.010)
    assert rec.finish(d, "error", "DeadlineExceeded: boom") is True
    (entry,) = rec.retained()
    assert entry["status"] == "error"
    assert entry["detail"].startswith("DeadlineExceeded")
    assert _sum_to_wall(entry)


def test_degraded_path_retains_with_events():
    clk = FakeClock()
    rec = DiaryRecorder(clock=clk)
    d = rec.start("pull")
    with reqtrace.stage("breaker", clock=clk):
        clk.advance(0.0001)
    with reqtrace.stage("wire", clock=clk):
        clk.advance(0.002)
    reqtrace.event("degraded", mode="replica")
    assert rec.finish(d, "degraded") is True
    (entry,) = rec.retained()
    assert entry["status"] == "degraded"
    assert {"name": "degraded", "mode": "replica"} in entry["events"]
    assert _sum_to_wall(entry)


def test_hedge_win_shape_attributes_delay_to_hedge():
    # the _hedged_race shape after ISSUE 19: the pre-hedge wait on a
    # primary that never answers is attribute()d to `hedge` (it is the
    # hedge mechanism's transient), the race wait is a `hedge` stage,
    # and the win stamps hedge_win + degraded events
    clk = FakeClock()
    rec = DiaryRecorder(clock=clk)
    d = rec.start("pull", owner=0)
    clk.advance(0.004)
    reqtrace.attribute("hedge", 0.004)      # pre-hedge wait, timed out
    reqtrace.event("hedge_fired", owner=0)
    with reqtrace.stage("hedge", clock=clk):
        clk.advance(0.0015)                 # the race: replica answers
    reqtrace.event("hedge_win", owner=0)
    reqtrace.event("degraded", mode="replica")
    assert rec.finish(d, "degraded") is True
    (entry,) = rec.retained()
    assert _sum_to_wall(entry)
    assert entry["stages"]["hedge"] == pytest.approx(0.0055, abs=1e-9)
    named = {s: v for s, v in entry["stages"].items() if s != "other"}
    assert max(named, key=named.get) == "hedge"
    names = [e["name"] for e in entry["events"]]
    assert names == ["hedge_fired", "hedge_win", "degraded"]


def test_nested_diaries_each_keep_the_invariant():
    # tier opens tier_pull, transport opens pull on the same thread: a
    # stage lands in BOTH, each diary sums to its own wall
    clk = FakeClock()
    rec = DiaryRecorder(clock=clk)
    outer = rec.start("tier_pull")
    with reqtrace.stage("dedupe", clock=clk):
        clk.advance(0.001)
    inner = rec.start("pull")
    with reqtrace.stage("wire", clock=clk):
        clk.advance(0.006)
    assert rec.finish(inner, "error", "boom") is True
    clk.advance(0.0005)
    assert rec.finish(outer, "degraded") is True
    by_op = {e["op"]: e for e in rec.retained()}
    assert _sum_to_wall(by_op["pull"]) and _sum_to_wall(by_op["tier_pull"])
    assert by_op["pull"]["stages"]["wire"] == pytest.approx(0.006)
    assert by_op["tier_pull"]["stages"]["wire"] == pytest.approx(0.006)
    assert by_op["tier_pull"]["stages"]["dedupe"] == pytest.approx(0.001)
    # inner wall is a strict subset of outer wall
    assert by_op["pull"]["wall_s"] < by_op["tier_pull"]["wall_s"]


def test_unknown_stage_folds_into_other():
    clk = FakeClock()
    rec = DiaryRecorder(clock=clk)
    d = rec.start("pull")
    with reqtrace.stage("not_a_stage", clock=clk):
        clk.advance(0.003)
    assert rec.finish(d, "error") is True
    (entry,) = rec.retained()
    assert "not_a_stage" not in entry["stages"]
    assert entry["stages"]["other"] >= 0.003
    assert _sum_to_wall(entry)


def test_helpers_noop_without_an_open_diary():
    assert reqtrace.current() is None
    # the disabled path returns the SHARED null context (no allocation)
    assert reqtrace.stage("wire") is reqtrace._NULL_CTX
    reqtrace.event("ignored")               # must not raise
    reqtrace.attribute("wire", 1.0)         # must not raise


# ------------------------------------------------------------------ #
# tail-based sampling + bounded ring


def test_sampler_drops_fast_calls_and_retains_the_tail():
    clk = FakeClock()
    rec = DiaryRecorder(clock=clk)
    _arm(rec, clk, n=64, wall=0.001)
    snap = rec.snapshot()
    assert snap["finished"] == 64 and snap["retained"] == 0
    # at-threshold calls stay dropped (strictly-greater comparison)
    d = rec.start("pull")
    clk.advance(0.001)
    assert rec.finish(d) is False
    # a tail call retains
    d = rec.start("pull")
    with reqtrace.stage("wire", clock=clk):
        clk.advance(0.040)
    assert rec.finish(d) is True
    snap = rec.snapshot()
    assert snap["retained"] == 1
    assert snap["by_status"]["ok"] == 66
    assert snap["thresholds_s"]["pull"] == pytest.approx(0.001)


def test_fast_ok_calls_drop_during_warmup():
    clk = FakeClock()
    rec = DiaryRecorder(clock=clk)
    d = rec.start("pull")
    clk.advance(0.0005)
    # no threshold armed yet: an ok call cannot be judged slow -> drop
    assert rec.finish(d) is False
    assert rec.threshold_s("pull") is None


def test_retained_ring_is_bounded_under_load():
    clk = FakeClock()
    rec = DiaryRecorder(ring=16, clock=clk)
    for i in range(200):
        d = rec.start("pull", i=i)
        clk.advance(0.001)
        rec.finish(d, "error", f"e{i}")
    snap = rec.snapshot()
    assert snap["retained"] == 200          # counted
    assert snap["ring_len"] == 16           # bounded
    ring = rec.retained()
    assert len(ring) == 16
    # newest survive
    assert ring[-1]["detail"] == "e199" and ring[0]["detail"] == "e184"
    # cumulative attribution keeps the invariant total across eviction
    assert snap["slow_wall_s"] == pytest.approx(0.2, abs=1e-6)
    assert sum(snap["attribution"].values()) == pytest.approx(
        0.2, abs=1e-6)


def test_abandon_records_nothing():
    clk = FakeClock()
    rec = DiaryRecorder(clock=clk)
    d = rec.start("pull")
    rec.abandon(d)
    assert reqtrace.current() is None
    assert rec.snapshot()["finished"] == 0


# ------------------------------------------------------------------ #
# flight bundles + the incident CLI


def _spin(dt):
    import time

    t0 = time.perf_counter()
    while time.perf_counter() - t0 < dt:
        pass


def _populate_singleton():
    # the singleton runs on the real monotonic clock, so stage time is
    # real elapsed time — attribution must never exceed the wall
    rec = reqtrace.get_recorder()
    d = rec.start("pull", owner=0)
    with reqtrace.stage("wire"):
        _spin(0.002)
    rec.finish(d, "error", "boom")
    d = rec.start("pull", owner=1)
    with reqtrace.stage("hedge"):
        # ten times the wire stage, not twice: under six loaded workers a
        # preemption inside the 2 ms spin made "wire" the dominant stage
        _spin(0.02)
    reqtrace.event("hedge_win", owner=1)
    rec.finish(d, "degraded")
    return rec


def test_diaries_ride_flight_bundles_replay_identical():
    rec = _populate_singleton()
    bundle = flight.FlightRecorder(ring=8, role="t").bundle("unit")
    block = bundle["diaries"]
    assert block["schema"] == 1
    assert block["retained"] == 2 and block["finished"] == 2
    # replay-identical: the bundle's worst calls ARE the ring entries
    worst = sorted(rec.retained(), key=lambda r: r["wall_s"],
                   reverse=True)[:BUNDLE_SLOW_CALLS]
    assert block["slow_calls"] == worst
    # and they survive a JSON round-trip bit-for-bit (pure JSON types)
    assert json.loads(json.dumps(block)) == block


def test_empty_recorder_contributes_no_bundle_block():
    assert reqtrace.get_recorder().bundle_block() is None
    bundle = flight.FlightRecorder(ring=8, role="t").bundle("unit")
    assert "diaries" not in bundle


def test_incident_slow_calls_section(tmp_path):
    from elasticdl_tpu.observability import incident

    _populate_singleton()
    bundle = flight.FlightRecorder(ring=8, role="t").bundle("unit")
    path = tmp_path / "flight-t-1.json"
    path.write_text(json.dumps(bundle, default=repr))
    report = incident.correlate([str(path)])
    sc = report["slow_calls"]
    assert sc["retained"] == 2
    assert sc["dominant_stage"] == "hedge"
    assert len(sc["calls"]) == 2
    assert all(c["role"] == "t" for c in sc["calls"])
    # strict-clean: every diary keeps the sum-to-wall invariant
    assert not [v for v in report["strict_violations"]
                if "diary" in str(v.get("problem", ""))]
    # the text rendering names the section and draws waterfalls
    text = incident.render_text(report)
    assert "slow_calls:" in text and "hedge" in text


def test_incident_strict_flags_sum_to_wall_violation(tmp_path):
    from elasticdl_tpu.observability import incident

    _populate_singleton()
    bundle = flight.FlightRecorder(ring=8, role="t").bundle("unit")
    # corrupt one diary: stages no longer sum to the wall
    bundle["diaries"]["slow_calls"][0]["wall_s"] = 5.0
    path = tmp_path / "flight-t-1.json"
    path.write_text(json.dumps(bundle, default=repr))
    report = incident.correlate([str(path)])
    viol = [v for v in report["strict_violations"]
            if "diary" in str(v.get("problem", ""))]
    assert len(viol) == 1
    assert "!= wall" in viol[0]["problem"]


# ------------------------------------------------------------------ #
# heartbeat payload + fleet rollup


def test_payload_names_the_dominant_stage():
    clk = FakeClock()
    rec = DiaryRecorder(clock=clk)
    d = rec.start("pull")
    with reqtrace.stage("budget_wait", clock=clk):
        clk.advance(0.008)
    with reqtrace.stage("wire", clock=clk):
        clk.advance(0.002)
    rec.finish(d, "degraded")
    p = rec.payload()
    assert p["rt_slow"] == 1.0
    assert STAGES[int(p["rt_dom"])] == "budget_wait"
    assert p["rt_dom_share"] == pytest.approx(0.8, abs=0.01)
    assert p["rt_known_share"] == pytest.approx(1.0, abs=0.01)
    # windowed degraded share appears from the second payload on
    d = rec.start("pull")
    clk.advance(0.001)
    rec.finish(d, "degraded")
    p2 = rec.payload()
    assert p2["emb_degraded_share"] == 1.0


def test_payload_empty_without_retained_tail():
    rec = DiaryRecorder()
    p = rec.payload()
    assert "rt_slow" not in p and "rt_dom" not in p


def test_fleet_attribution_shift_pulses_once():
    fleet = FleetAttribution()
    wire, hedge = STAGES.index("wire"), STAGES.index("hedge")

    def recs(dom):
        return [
            {"updated_at": 1000.0, "rt_slow_wall_s": 2.0,
             "rt_dom": dom, "rt_known_share": 0.9},
            # stale reporter: ignored even with a larger wall
            {"updated_at": 1.0, "rt_slow_wall_s": 9.0,
             "rt_dom": (dom + 1) % len(STAGES)},
        ]

    s1 = fleet.series(recs(wire), now=1010.0)
    assert s1["edl_fleet_emb_attr_dom_stage"] == float(wire)
    assert s1["edl_fleet_emb_attr_dom_shift"] == 0.0   # first sighting
    s2 = fleet.series(recs(wire), now=1010.0)
    assert s2["edl_fleet_emb_attr_dom_shift"] == 0.0   # steady
    s3 = fleet.series(recs(hedge), now=1010.0)
    assert s3["edl_fleet_emb_attr_dom_shift"] == 1.0   # the pulse
    assert s3["edl_fleet_emb_attr_dom_stage"] == float(hedge)
    assert s3["edl_fleet_emb_attr_known_share"] == 0.9
    s4 = fleet.series(recs(hedge), now=1010.0)
    assert s4["edl_fleet_emb_attr_dom_shift"] == 0.0
    # no fresh reporters -> no series at all (no-data, never zero)
    assert fleet.series(recs(wire)[1:], now=1010.0) == {}


def test_dom_shift_alert_rule_is_default():
    from elasticdl_tpu.observability import alerts

    rules = {r.name: r for r in alerts.default_rules()}
    rule = rules["emb_attr_dominant_shift"]
    assert rule.series == "edl_fleet_emb_attr_dom_shift"
    assert rule.mode == "value" and rule.threshold == 0.5
