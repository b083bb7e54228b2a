"""Chaos: seeded fault schedules against the real control plane.

Two tiers:

- The SMOKE (tier-1, marker `chaos`): the full master control plane —
  TaskDispatcher + Membership + MasterServicer behind a real gRPC server —
  driven by a deterministic single-threaded worker through the hardened
  RetryingMasterStub, under a schedule of drops, delays, and lost
  responses. Run twice with the same seed: the injected-fault traces and
  the task-accounting traces must be IDENTICAL, and each run must retire
  every shard span exactly once with zero permanent failures.

- The SOAK (markers `chaos slow`): real worker subprocesses training
  synthetic MNIST under an env-delivered schedule that drops get_task,
  delays reports, and hard-kills the worker mid-checkpoint-write
  (ckpt.save.commit:crash) — every relaunched generation must restore and
  the job must complete with exactly-once task accounting.
"""

import json
import os
import random
import time

import pytest

from elasticdl_tpu.analysis.lockorder import LockOrderRecorder, instrument_master
from elasticdl_tpu.common import faults
from elasticdl_tpu.master.membership import Membership
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.proto.service import (
    CircuitBreaker,
    RetryingMasterStub,
    add_master_servicer,
    make_channel,
    make_server,
)


@pytest.fixture(autouse=True)
def _clean_injector():
    faults.reset()
    yield
    faults.reset()


SMOKE_SPEC = (
    "rpc.get_task:drop@p=0.25;"
    "rpc.get_task:delay@ms=1,p=0.2;"
    "rpc.heartbeat:drop@every=3;"
    "rpc.report_task_result.recv:drop@at=2"
)

SHARDS = [("s0", 0, 200), ("s1", 0, 160)]


def run_control_plane_scenario(seed: int):
    """One full job through the real gRPC wire under SMOKE_SPEC.

    Single-threaded by construction (heartbeats are driven from the same
    loop, no background threads, no wall-clock triggers), so the RPC call
    sequence — and with it every seeded fault decision — is a pure
    function of the seed.

    With EDL_CHAOS_ARTIFACT_DIR set (CI), the scenario's trace.jsonl, a
    /metrics snapshot, and the cluster-health rollup snapshot are written
    there for workflow-artifact upload — the chaos run's observability
    record, not just its assertions.

    The worker's heartbeats carry the REAL telemetry payload (ISSUE 7)
    while the schedule is dropping heartbeats around them: the health
    rollup must come up coherent from whatever beats survive.
    """
    import json as _json

    from elasticdl_tpu.observability import health as health_lib
    from elasticdl_tpu.observability import tracing
    from elasticdl_tpu.observability.alerts import AlertEngine, default_rules
    from elasticdl_tpu.observability.health import ClusterHealth
    from elasticdl_tpu.observability.registry import default_registry
    from elasticdl_tpu.observability.timeseries import (
        TimeSeriesStore,
        fleet_series,
    )

    art_dir = os.environ.get("EDL_CHAOS_ARTIFACT_DIR")
    flight_rec = None
    if art_dir:
        os.makedirs(art_dir, exist_ok=True)
        tracing.configure(
            path=os.path.join(art_dir, f"chaos-smoke-seed{seed}.trace.jsonl"),
            role="chaos-smoke",
        )
        # the smoke's flight recorder (ISSUE 9): subscribes to the tracer
        # so the run's spans/events fill its ring; dumped at scenario end
        # and correlated by CI's incident-CLI --strict pass
        from elasticdl_tpu.observability.flight import FlightRecorder

        flight_rec = FlightRecorder(role=f"chaos-smoke-seed{seed}")
        flight_rec.configure(dir=art_dir, seed=seed)
        flight_rec.attach_tracing()
    faults.install(SMOKE_SPEC, seed=seed)
    dispatcher = TaskDispatcher(
        training_shards=SHARDS, records_per_task=40, shuffle=True,
        shuffle_seed=seed, task_timeout_s=1e9,
    )
    membership = Membership(heartbeat_timeout_s=1e9)
    membership.add_death_callback(dispatcher.recover_tasks)
    servicer = MasterServicer(dispatcher, membership, None)
    cluster_health = ClusterHealth(membership)
    step_stats = health_lib.WorkerStepStats()
    # observe->decide backbone riding the chaos schedule (ISSUE 11): a
    # time-series ring sampled on a deterministic iteration cadence +
    # the default alert rules evaluated against it — the run's rolling
    # metrics_history.jsonl and alerts.json upload with the other
    # artifacts (values are wall-clock noise; the artifact's point is
    # the PLUMBING surviving chaos, and no assertion reads them)
    ts_store = TimeSeriesStore(
        capacity=512, interval_s=0.0,
        history_path=(os.path.join(
            art_dir, f"chaos-smoke-seed{seed}.metrics_history.jsonl")
            if art_dir else None),
    )
    alert_engine = AlertEngine(
        ts_store, rules=default_rules(),
        json_path=(os.path.join(
            art_dir, f"chaos-smoke-seed{seed}.alerts.json")
            if art_dir else None),
        flight_dump=lambda reason: None,
    )
    # lock-order recording rides the whole scenario: any inversion
    # introduced into the control plane raises at its acquire site, and
    # the graph is certified acyclic before the scenario returns
    lock_rec = LockOrderRecorder(raise_on_cycle=True)
    instrument_master(
        lock_rec, membership=membership, dispatcher=dispatcher,
        servicer=servicer,
    )
    server = make_server()
    add_master_servicer(server, servicer)
    port = server.add_insecure_port("localhost:0")
    assert port, "could not bind an ephemeral port"
    server.start()
    channel = make_channel(f"localhost:{port}")
    stub = RetryingMasterStub(
        channel,
        rng=random.Random(seed),
        sleep=lambda s: None,              # keep the smoke wall-clock-free
        breaker=CircuitBreaker(cooldown_s=0.0),
    )
    applied = []                           # (shard, start, end) spans retired
    try:
        wid = stub.RegisterWorker(
            pb.RegisterWorkerRequest(worker_name="chaos-smoke")
        ).worker_id
        for it in range(10_000):           # livelock guard
            if it % 50 == 0:
                ts_store.sample(extra=fleet_series(
                    membership.health_snapshot(),
                    straggler_count=cluster_health.snapshot().get(
                        "straggler_count", 0),
                    todo_tasks=dispatcher.counts()["todo"],
                    alive_workers=membership.alive_count(),
                ))
                alert_engine.evaluate()
            try:
                stub.Heartbeat(
                    pb.HeartbeatRequest(worker_id=wid),
                    metadata=((
                        health_lib.STATS_METADATA_KEY,
                        health_lib.encode_stats(
                            dict(step_stats.snapshot(), phase="train")
                        ),
                    ),),
                )
            except Exception:
                pass                       # dropped heartbeats are survivable
            cluster_health.update()
            try:
                resp = stub.GetTask(pb.GetTaskRequest(worker_id=wid))
            except Exception:
                continue                   # dropped lease: ask again
            if resp.job_done:
                break
            task = resp.task
            if task.type == pb.WAIT:
                continue
            # "train" the task: the telemetry window sees one step per
            # span (values are wall-clock noise; the artifact's point is
            # the PLUMBING surviving chaos, and the assertions below never
            # read them — determinism holds)
            t_step = time.perf_counter()
            applied.append((task.shard_name, task.start, task.end))
            step_stats.observe_step(
                time.perf_counter() - t_step, records=task.end - task.start
            )
            try:
                stub.ReportTaskResult(
                    pb.ReportTaskResultRequest(
                        worker_id=wid, task_id=task.task_id, success=True,
                    )
                )
            except Exception:
                # lost RESPONSE (rpc.report_task_result.recv): the server
                # retired the task; the worker just never heard back
                pass
        else:
            pytest.fail("chaos smoke livelocked")
        counts = dispatcher.counts()
        trace = list(faults.get_injector().trace)
        lock_rec.assert_no_cycles()
    finally:
        channel.close()
        server.stop(None)
        faults.uninstall()
        if art_dir:
            tracing.get_tracer().close()
            flight_rec.dump("chaos_smoke")
            flight_rec.detach_tracing()
            with open(
                os.path.join(art_dir, f"chaos-smoke-seed{seed}.metrics.prom"),
                "w",
            ) as f:
                f.write(default_registry().render_prometheus())
            # the cluster-health rollup the run ended with (ISSUE 7):
            # uploaded next to trace + metrics so a chaos regression in
            # the telemetry path ships its own fleet-health evidence.
            # snapshot() (not the raw update() dict) so the serialized
            # rollup carries snapshot_age_s (ISSUE 11) — the incident
            # CLI prints the age next to each snapshot it correlates
            cluster_health.update()
            with open(
                os.path.join(art_dir, f"chaos-smoke-seed{seed}.health.json"),
                "w",
            ) as f:
                _json.dump(cluster_health.snapshot(), f, indent=2,
                           sort_keys=True)
            # terminal alert state (alerts.json also lands on every
            # transition during the run)
            alert_engine.write_json()
    return applied, counts, trace


@pytest.mark.chaos
def test_chaos_smoke_deterministic_and_exactly_once():
    applied_a, counts_a, trace_a = run_control_plane_scenario(seed=1234)
    applied_b, counts_b, trace_b = run_control_plane_scenario(seed=1234)

    # determinism: same seed + spec => the same injected fault sequence and
    # the same task-accounting trace, down to the order
    assert trace_a == trace_b
    assert applied_a == applied_b
    assert counts_a == counts_b

    # the schedule actually did something
    assert any("drop" in line for line in trace_a), trace_a

    # hardening held: no permanent failures, every span retired exactly once
    assert counts_a["failed_permanently"] == 0
    assert counts_a["doing"] == 0 and counts_a["todo"] == 0
    assert counts_a["finished_training"] == 9       # 200/40 + 160/40
    for shard, _, length in SHARDS:
        marks = [0] * length
        for s, a, b in applied_a:
            if s == shard:
                for i in range(a, b):
                    marks[i] += 1
        bad = [i for i, m in enumerate(marks) if m != 1]
        assert not bad, (shard, bad[:10])


@pytest.mark.chaos
def test_chaos_smoke_different_seed_changes_schedule():
    _, _, trace_a = run_control_plane_scenario(seed=1)
    _, _, trace_b = run_control_plane_scenario(seed=2)
    assert trace_a != trace_b


# ---------------------------------------------------------------------- #
# full soak: real processes, real checkpoint crashes


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_soak_e2e(tmp_path):
    from elasticdl_tpu.client.local import free_port
    from elasticdl_tpu.common.config import JobConfig
    from tests.jobs import run_job

    trace_path = tmp_path / "fault_trace"
    soak_spec = (
        "rpc.get_task:drop@p=0.1;"
        "rpc.heartbeat:drop@p=0.1;"
        "rpc.report_task_result:delay@ms=50,p=0.3;"
        # hard worker kill with the checkpoint write in flight: each
        # generation's 2nd save dies mid-air; the relaunch must restore
        # (walking back past any uncommitted step) and keep going
        "ckpt.save.commit:crash@at=2"
    )
    env = {
        faults.FAULTS_ENV: soak_spec,
        faults.SEED_ENV: "7",
        faults.TRACE_ENV: str(trace_path),
    }
    cfg = JobConfig(
        job_name="chaos-soak",
        job_type="training_only",
        model_zoo=os.path.abspath("model_zoo"),
        model_def="mnist.mnist_cnn.custom_model",
        model_params={"learning_rate": 0.01},
        training_data="synthetic://mnist?n=400&shards=4",
        records_per_task=100,
        minibatch_size=32,
        num_epochs=1,
        num_workers=1,
        master_addr=f"localhost:{free_port()}",
        worker_heartbeat_s=0.5,
        task_timeout_s=60.0,
        shuffle=False,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=3,
        relaunch_max=5,
    )
    _, manager, counts = run_job(cfg, tmp_path, extra_env=env)
    log = (tmp_path / "logs" / "worker-0.log").read_text()
    # exactly-once task accounting under the whole schedule
    assert counts["failed_permanently"] == 0, counts
    assert counts["finished_training"] == 4, counts
    assert counts["todo"] == 0 and counts["doing"] == 0, counts
    # the schedule really fired: the worker died mid-checkpoint-write
    # at least once and a relaunched generation restored state
    trace = trace_path.read_text() if trace_path.exists() else ""
    assert "ckpt.save.commit:crash" in trace, trace
    assert "resumed from checkpoint" in log
    deadline = time.time() + 30
    while not manager.all_exited() and time.time() < deadline:
        time.sleep(0.5)
    assert manager.all_exited()


# ---------------------------------------------------------------------- #
# proc.spawn site (the injection point lives in the MASTER process)


@pytest.mark.chaos
def test_spawn_fault_site_spawns_doomed_process(tmp_path):
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.master.process_manager import ProcessManager

    faults.install("proc.spawn:drop@at=1")
    cfg = JobConfig(
        model_def="mnist.mnist_cnn.custom_model", num_workers=1,
        master_addr="localhost:1",
    )
    manager = ProcessManager(cfg, log_dir=str(tmp_path))
    wp = manager._spawn(0)
    assert wp.proc.wait(timeout=30) == 1       # the doomed stand-in died
    # the next spawn is a real worker again (kill it before it connects)
    wp2 = manager._spawn(0)
    assert wp2.proc.poll() is None
    wp2.proc.kill()
    wp2.proc.wait(timeout=30)


# ---------------------------------------------------------------------- #
# kill-the-master (ISSUE 5): journal replay + generation-fenced reconnect


def run_master_restart_scenario(seed: int, ckpt_dir: str, crash_at: int,
                                tag: str = "", group_commit_ms: float = 0.0):
    """One full job where the master is killed mid-epoch and restarted.

    The worker is the SAME single-threaded loop throughout (no process
    restart): it survives the crash through the generation handshake —
    fenced RPCs trigger an idempotent re-register, then it re-leases. The
    successor master replays the control-plane journal, so the in-flight
    lease at crash time is conservatively requeued and retired exactly
    once. `crash_at=0` runs the uncrashed baseline the accounting is
    compared against.

    Incident evidence (ISSUE 9): master and worker each run a flight
    recorder (observability/flight.py); the crash cuts the master's
    black box, the scenario end cuts the worker's (whose ring carries
    the reconnect), and both bundles land under <flight_dir> — the
    artifact dir in CI, <ckpt_dir>/flight otherwise — where the incident
    CLI correlates them into one timeline.

    With EDL_CHAOS_ARTIFACT_DIR set (CI), the replayed journal and the
    recovery trace/metrics land there for workflow-artifact upload.
    """
    import shutil

    from elasticdl_tpu.master.journal import ControlPlaneJournal
    from elasticdl_tpu.observability import tracing
    from elasticdl_tpu.observability.flight import FlightRecorder
    from elasticdl_tpu.observability.registry import default_registry
    from elasticdl_tpu.proto.service import REREGISTER_KEY, is_stale_generation

    art_dir = os.environ.get("EDL_CHAOS_ARTIFACT_DIR")
    stem = f"master-kill-{tag or 'run'}-seed{seed}"
    if art_dir:
        os.makedirs(art_dir, exist_ok=True)
        tracing.configure(
            path=os.path.join(art_dir, f"{stem}.trace.jsonl"),
            role="chaos-master-kill",
        )
    flight_dir = art_dir or os.path.join(ckpt_dir, "flight")
    # both roles live in this process, so each gets its OWN recorder (the
    # singleton is per-process); the master's subscribes to the tracer so
    # control-plane events land in its ring at full fidelity
    master_flight = FlightRecorder(role="master").configure(
        dir=flight_dir, tag=stem, scenario=stem)
    master_flight.attach_tracing()
    worker_flight = FlightRecorder(role="worker-0").configure(
        dir=flight_dir, tag=stem, scenario=stem)
    spec = f"master_crash:drop@at={crash_at}" if crash_at else ""
    faults.install(spec, seed=seed)

    def boot(port=0):
        journal = ControlPlaneJournal(ckpt_dir, group_commit_ms=group_commit_ms)
        dispatcher = TaskDispatcher(
            training_shards=SHARDS, records_per_task=40, shuffle=True,
            shuffle_seed=seed, task_timeout_s=1e9, journal=journal,
        )
        membership = Membership(heartbeat_timeout_s=1e9, journal=journal)
        membership.add_death_callback(dispatcher.recover_tasks)
        servicer = MasterServicer(
            dispatcher, membership, None, generation=journal.generation,
        )
        server = make_server()
        add_master_servicer(server, servicer)
        if port:
            # the successor must rebind the EXACT address the worker's
            # channel holds; with so_reuseport off the bind fails honestly
            # (0 or RuntimeError) until the crashed listener fully closes
            for _ in range(50):
                try:
                    bound = server.add_insecure_port(f"localhost:{port}")
                except RuntimeError:
                    bound = 0
                if bound:
                    break
                time.sleep(0.1)
            else:
                pytest.fail(f"successor master could not rebind :{port}")
        else:
            port = server.add_insecure_port("localhost:0")
            assert port, "could not bind an ephemeral port"
        server.start()
        return journal, dispatcher, membership, servicer, server, port

    journal, dispatcher, membership, servicer, server, port = boot()
    channel = make_channel(f"localhost:{port}")
    stub = RetryingMasterStub(
        channel,
        rng=random.Random(seed),
        sleep=lambda s: None,
        breaker=CircuitBreaker(cooldown_s=0.0),
    )
    applied = []        # (shard, start, end) spans the MASTER accepted
    reconnects = 0
    restarts = 0

    def reregister(wid):
        # the reconnect handshake, exactly as worker.py runs it: clear the
        # stale claim, re-register under the existing id with the marker
        stub.generation = None
        new_wid = stub.RegisterWorker(
            pb.RegisterWorkerRequest(
                worker_name="chaos-master-kill",
                preferred_id_plus_one=wid + 1,
            ),
            metadata=((REREGISTER_KEY, "1"),),
        ).worker_id
        # what worker.py's _on_reregistered records via tracing.event — this
        # single-threaded twin records it straight into its ring
        worker_flight.record(
            "event", "worker.reconnect", worker_id=new_wid,
            generation=stub.generation,
        )
        return new_wid

    try:
        wid = stub.RegisterWorker(
            pb.RegisterWorkerRequest(worker_name="chaos-master-kill")
        ).worker_id
        for _ in range(10_000):            # livelock guard
            try:
                stub.Heartbeat(pb.HeartbeatRequest(worker_id=wid))
            except Exception as e:
                if is_stale_generation(e):
                    wid = reregister(wid)
                    reconnects += 1
            try:
                resp = stub.GetTask(pb.GetTaskRequest(worker_id=wid))
            except Exception as e:
                if is_stale_generation(e):
                    wid = reregister(wid)
                    reconnects += 1
                continue
            if resp.job_done:
                break
            task = resp.task
            if task.type == pb.WAIT:
                continue
            try:
                # the kill site sits between lease and report, so the
                # crash always strands an in-flight lease — the hard case
                faults.fire("master_crash")
            except faults.FaultInjected:
                # the chaos driver's half: abrupt death (no shutdown
                # handshake, no worker teardown), then a successor boots
                # from the journal on the same address. abort(), not
                # close(): queued-but-unacknowledged group commits must
                # DROP, exactly as SIGKILL would drop them
                server.stop(None).wait(5)
                journal.abort()
                # the black box survives the kill (Master.crash does the
                # same dump for in-process masters)
                master_flight.record(
                    "event", "master.crash", generation=journal.generation,
                )
                master_flight.dump("master_crash")
                journal, dispatcher, membership, servicer, server, port = (
                    boot(port)
                )
                master_flight.record(
                    "event", "master.recovered",
                    generation=journal.generation,
                )
                restarts += 1
            try:
                r = stub.ReportTaskResult(
                    pb.ReportTaskResultRequest(
                        worker_id=wid, task_id=task.task_id, success=True,
                    )
                )
            except Exception as e:
                # fenced report from before the crash: the replayed queue
                # requeued this lease whole — never resend, re-register
                # and re-lease instead (exactly worker.py's triage)
                if is_stale_generation(e):
                    wid = reregister(wid)
                    reconnects += 1
                continue
            if r.accepted:
                applied.append((task.shard_name, task.start, task.end))
        else:
            pytest.fail("master-kill smoke livelocked")
        counts = dispatcher.counts()
        trace = list(faults.get_injector().trace)
    finally:
        channel.close()
        server.stop(None)
        journal.close()
        faults.uninstall()
        # the worker's black box is cut by an explicit end-of-scenario
        # trigger (its ring carries the reconnect handshake(s)); the
        # master dumped at crash time — for the uncrashed baseline, dump
        # it here too so every run leaves a master bundle
        worker_flight.dump("scenario_end")
        if master_flight.last_dump_path is None:
            master_flight.dump("scenario_end")
        master_flight.detach_tracing()
        if art_dir:
            tracing.get_tracer().close()
            shutil.copyfile(
                os.path.join(ckpt_dir, "control", "journal.jsonl"),
                os.path.join(art_dir, f"{stem}.journal.jsonl"),
            )
            with open(
                os.path.join(art_dir, f"{stem}.metrics.prom"), "w"
            ) as f:
                f.write(default_registry().render_prometheus())
    return {
        "flight_dir": flight_dir,
        "applied": applied,
        "counts": counts,
        "trace": trace,
        "generation": journal.generation,
        "stub_generation": stub.generation,
        "worker_id": wid,
        "alive": membership.alive_count(),
        "reconnects": reconnects,
        "restarts": restarts,
    }


@pytest.mark.chaos
def test_kill_master_smoke_exactly_once_and_deterministic(tmp_path):
    base = run_master_restart_scenario(
        seed=77, ckpt_dir=str(tmp_path / "base"), crash_at=0, tag="base"
    )
    run_a = run_master_restart_scenario(
        seed=77, ckpt_dir=str(tmp_path / "a"), crash_at=5, tag="a"
    )
    run_b = run_master_restart_scenario(
        seed=77, ckpt_dir=str(tmp_path / "b"), crash_at=5, tag="b"
    )

    # deterministic twice in a row: same fault schedule, same accepted-task
    # trace, same final accounting
    assert run_a["trace"] == run_b["trace"] == ["master_crash:drop#5"]
    assert run_a["applied"] == run_b["applied"]
    assert run_a["counts"] == run_b["counts"]

    for run in (run_a, run_b):
        # the master really died and came back under generation N+1, and
        # the worker reconnected in place (same id, no duplicate member)
        assert run["restarts"] == 1 and run["generation"] == 2
        assert run["reconnects"] >= 1
        assert run["stub_generation"] == 2     # handshake landed
        assert run["worker_id"] == base["worker_id"]
        assert run["alive"] == 1
        # exactly-once accounting held ACROSS the crash…
        assert run["counts"]["failed_permanently"] == 0
        assert run["counts"]["todo"] == 0 and run["counts"]["doing"] == 0
        # …and the completed-task trace equals the uncrashed run's (the
        # requeue changes the order, never the set)
        assert sorted(run["applied"]) == sorted(base["applied"])
        assert run["counts"] == base["counts"]

    assert base["restarts"] == 0 and base["generation"] == 1
    assert base["counts"]["finished_training"] == 9      # 200/40 + 160/40
    for shard, _, length in SHARDS:
        marks = [0] * length
        for s, a, b in run_a["applied"]:
            if s == shard:
                for i in range(a, b):
                    marks[i] += 1
        bad = [i for i, m in enumerate(marks) if m != 1]
        assert not bad, (shard, bad[:10])


@pytest.mark.chaos
def test_kill_master_smoke_group_commit_mode_identical(tmp_path):
    """ISSUE 8 acceptance: kill-master replay accounting must be
    IDENTICAL across commit modes. The same seeded scenario runs with
    `--journal_group_commit_ms` > 0 — same fault schedule, same
    accepted-task set, same final counts as the per-commit twin, because
    group commit changes only how records pack into fsyncs: everything
    acknowledged is still durable (ack-after-fsync), and what the abrupt
    death drops was never acknowledged to the worker."""
    per = run_master_restart_scenario(
        seed=77, ckpt_dir=str(tmp_path / "per"), crash_at=5, tag="per",
    )
    grp = run_master_restart_scenario(
        seed=77, ckpt_dir=str(tmp_path / "grp"), crash_at=5, tag="grp",
        group_commit_ms=5.0,
    )
    assert grp["trace"] == per["trace"] == ["master_crash:drop#5"]
    # the acceptance identity: accounting does not depend on commit mode
    assert grp["applied"] == per["applied"]
    assert grp["counts"] == per["counts"]
    assert grp["restarts"] == 1 and grp["generation"] == 2
    assert grp["stub_generation"] == 2
    assert grp["counts"]["failed_permanently"] == 0
    assert grp["counts"]["todo"] == 0 and grp["counts"]["doing"] == 0
    # exactly-once span coverage under group commit
    for shard, _, length in SHARDS:
        marks = [0] * length
        for s, a, b in grp["applied"]:
            if s == shard:
                for i in range(a, b):
                    marks[i] += 1
        bad = [i for i, m in enumerate(marks) if m != 1]
        assert not bad, (shard, bad[:10])


@pytest.mark.chaos
def test_kill_master_produces_incident_bundles(tmp_path, capsys):
    """ISSUE 9 acceptance: a kill-master chaos run leaves flight bundles
    from the master AND >= 1 worker, and the incident CLI merges them
    into ONE timeline that places the crash and the reconnect on it (in
    that order), exiting 0 under --strict."""
    import glob

    from elasticdl_tpu.observability import incident

    run = run_master_restart_scenario(
        seed=77, ckpt_dir=str(tmp_path / "ckpt"), crash_at=5, tag="flight",
    )
    assert run["restarts"] == 1 and run["reconnects"] >= 1

    flight_dir = run["flight_dir"]
    bundles = sorted(glob.glob(os.path.join(flight_dir, "flight-*.json")))
    roles = set()
    for path in bundles:
        with open(path) as f:
            roles.add(json.load(f)["role"])
    assert "master" in roles, bundles
    assert any(r.startswith("worker") for r in roles), bundles

    report = incident.correlate([flight_dir])
    # the tracer stamps its own role on sunk records (e.g. the CI
    # artifact run's "chaos-master-kill"), so containment, not equality
    assert {"master", "worker-0"} <= set(report["roles"])
    names = [e["name"] for e in report["timeline"]]
    assert "master.crash" in names and "worker.reconnect" in names
    # the merged ordering is the story: the crash comes first, the
    # reconnect follows it on the same timeline
    assert names.index("master.crash") < names.index("worker.reconnect")
    # the master's crash-time bundle is ON the timeline too (its dump)
    crash_dumps = [
        e for e in report["timeline"]
        if e["kind"] == "dump" and e.get("reason") == "master_crash"
    ]
    assert crash_dumps and crash_dumps[0]["role"] == "master"

    # CLI contract: text render names both, --strict exits 0 over the
    # atomically-written bundles, --json round-trips
    rc = incident.main([flight_dir, "--strict"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "master.crash" in out and "worker.reconnect" in out
    rc = incident.main([flight_dir, "--json"])
    json.loads(capsys.readouterr().out)
    assert rc == 0


@pytest.mark.chaos
@pytest.mark.slow
def test_master_restart_e2e(tmp_path):
    """Full-stack master kill: run_local with --master_restarts, a REAL
    worker subprocess training through the crash. The master_crash drop
    fires inside Master.wait; the launcher crashes the master abruptly,
    rebuilds it on the same port, and the worker reconnects under
    generation 2 without being restarted."""
    from elasticdl_tpu.client.local import free_port, run_local
    from elasticdl_tpu.common.config import JobConfig
    from tests.jobs import HERMETIC_ENV

    faults.install("master_crash:drop@at=4")
    cfg = JobConfig(
        job_name="master-kill-e2e",
        job_type="training_only",
        model_zoo=os.path.abspath("model_zoo"),
        model_def="mnist.mnist_cnn.custom_model",
        model_params={"learning_rate": 0.01},
        training_data="synthetic://mnist?n=400&shards=4",
        records_per_task=100,
        minibatch_size=32,
        num_epochs=1,
        num_workers=1,
        master_addr=f"localhost:{free_port()}",
        worker_heartbeat_s=0.5,
        task_timeout_s=60.0,
        shuffle=False,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=3,
        relaunch_max=3,
        master_restarts=1,
    )
    rc = run_local(
        cfg, extra_env=HERMETIC_ENV, log_dir=str(tmp_path / "logs"),
        timeout_s=420,
    )
    log = (tmp_path / "logs" / "worker-0.log").read_text()
    assert rc == 0, "e2e did not finish; worker log:\n" + log[-6000:]
    # the worker process rode through the crash WITHOUT a process restart.
    # Which reconnect flavor it hit depends on boot timing vs the crash
    # poll (1-core box: jax import can outlast the fault's wait-loop
    # countdown): mid-job -> fenced RPC + idempotent re-register; still
    # booting -> register_with_retry rides out the restart window. Both
    # prove crash-survival without burning the relaunch budget (the
    # deterministic mid-job re-register is covered by the kill-master
    # smoke above, which drives the handshake at the RPC level).
    assert (
        "re-registered with restarted master" in log
        or "boot registration failed" in log
    )
    assert "exiting EX_TEMPFAIL" not in log
    # the successor really replayed the journal under generation 2; a
    # cleanly finished job retires its journal (resubmission with this
    # checkpoint_dir must not replay job_end and no-op) but keeps the
    # final state on disk for forensics
    journal_dir = tmp_path / "ckpt" / "control"
    assert not (journal_dir / "journal.jsonl").exists()
    completed = journal_dir / "journal.jsonl.completed"
    header = json.loads(completed.read_text().splitlines()[0])
    assert header["generation"] == 2
