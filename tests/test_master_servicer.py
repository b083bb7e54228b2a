"""Master control plane over a real local gRPC channel in one process —
the reference's key test trick (SURVEY §4: in-process fakes, local channels)."""

import grpc
import numpy as np
import pytest

from elasticdl_tpu.master.evaluation_service import EvaluationService
from elasticdl_tpu.master.membership import Membership
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.proto.service import (
    MasterStub,
    add_master_servicer,
    is_stale_generation,
    make_channel,
    make_server,
)
from elasticdl_tpu.training import metrics as metrics_lib


@pytest.fixture()
def master_stack():
    dispatcher = TaskDispatcher(
        training_shards=[("t", 0, 40)],
        evaluation_shards=[("v", 0, 8)],
        records_per_task=10,
        shuffle=False,
    )
    membership = Membership(heartbeat_timeout_s=30)
    membership.add_death_callback(dispatcher.recover_tasks)
    metrics = {"accuracy": metrics_lib.Accuracy()}
    evaluation = EvaluationService(dispatcher, metrics, evaluation_steps=2)
    servicer = MasterServicer(dispatcher, membership, evaluation)
    server = make_server()
    add_master_servicer(server, servicer)
    port = server.add_insecure_port("[::]:0")
    server.start()
    stub = MasterStub(make_channel(f"localhost:{port}"))
    yield stub, dispatcher, membership, evaluation, servicer
    server.stop(0)


def test_register_and_lease(master_stack):
    stub, dispatcher, membership, *_ = master_stack
    r = stub.RegisterWorker(pb.RegisterWorkerRequest(worker_name="w"))
    assert r.worker_id == 0 and r.num_workers == 1
    resp = stub.GetTask(pb.GetTaskRequest(worker_id=r.worker_id))
    assert not resp.job_done
    assert resp.task.type == pb.TRAINING
    assert resp.task.end - resp.task.start == 10
    stub.ReportTaskResult(
        pb.ReportTaskResultRequest(
            worker_id=r.worker_id, task_id=resp.task.task_id, success=True,
            loss_sum=5.0, loss_count=10,
        )
    )
    assert dispatcher.counts()["finished_training"] == 1


def test_eval_cycle_over_grpc(master_stack):
    stub, dispatcher, membership, evaluation, _ = master_stack
    r = stub.RegisterWorker(pb.RegisterWorkerRequest(worker_name="w"))
    # evaluation_steps is in MODEL-VERSION steps (minibatches), the
    # reference's unit (round-3 fix): the worker-reported model_version
    # crossing the threshold triggers the eval job
    for version in (1, 2):
        resp = stub.GetTask(pb.GetTaskRequest(worker_id=r.worker_id))
        stub.ReportTaskResult(
            pb.ReportTaskResultRequest(
                worker_id=r.worker_id, task_id=resp.task.task_id, success=True,
                model_version=version,
            )
        )
    resp = stub.GetTask(pb.GetTaskRequest(worker_id=r.worker_id))
    assert resp.task.type == pb.EVALUATION
    # report metrics: 3 of 4 correct
    acc = metrics_lib.Accuracy()
    state = acc.init_state()
    state = np.asarray(
        acc.update(state, np.array([1, 1, 0, 0]), np.array([2.0, 3.0, -1.0, 2.0]))
    )
    msg = pb.ReportEvaluationMetricsRequest(
        worker_id=r.worker_id,
        eval_job_id=resp.task.eval_job_id,
        task_id=resp.task.task_id,
    )
    msg.states.append(pb.MetricState(name="accuracy", data=state.astype(np.float32).tobytes()))
    stub.ReportEvaluationMetrics(msg)
    stub.ReportTaskResult(
        pb.ReportTaskResultRequest(
            worker_id=r.worker_id, task_id=resp.task.task_id, success=True
        )
    )
    status = stub.GetJobStatus(pb.Empty())
    assert abs(status.eval_metrics["accuracy"] - 0.75) < 1e-6


def test_heartbeat_and_membership(master_stack):
    stub, dispatcher, membership, *_ , servicer = master_stack
    r0 = stub.RegisterWorker(pb.RegisterWorkerRequest(worker_name="w0"))
    r1 = stub.RegisterWorker(pb.RegisterWorkerRequest(worker_name="w1"))
    h = stub.Heartbeat(pb.HeartbeatRequest(worker_id=r0.worker_id, model_version=3))
    assert h.num_workers == 2 and not h.shutdown
    # lease a task to w1, declare it dead → task recovered
    resp = stub.GetTask(pb.GetTaskRequest(worker_id=r1.worker_id))
    membership.mark_dead(r1.worker_id, "test kill")
    h2 = stub.Heartbeat(pb.HeartbeatRequest(worker_id=r0.worker_id))
    assert h2.membership_version > h.membership_version
    assert h2.num_workers == 1
    # a heartbeat from a worker written off is a LIVE worker's: it is told
    # to re-register (the fence's rejection), not to shut down with tasks
    # left (ROADMAP C21)
    with pytest.raises(grpc.RpcError) as rejected:
        stub.Heartbeat(pb.HeartbeatRequest(worker_id=r1.worker_id))
    assert is_stale_generation(rejected.value)
    # ... unless the master itself is shutting down
    servicer.request_shutdown()
    assert stub.Heartbeat(pb.HeartbeatRequest(worker_id=r1.worker_id)).shutdown
    servicer._shutdown = False
    # recovered task is re-leasable
    resp2 = stub.GetTask(pb.GetTaskRequest(worker_id=r0.worker_id))
    assert resp2.task.task_id == resp.task.task_id


def test_heartbeat_carries_lr_override(master_stack):
    """ReduceLROnPlateau's push path: servicer.set_learning_rate shows up in
    every subsequent HeartbeatResponse (0 until set)."""
    stub, dispatcher, membership, *_, servicer = master_stack
    r0 = stub.RegisterWorker(pb.RegisterWorkerRequest(worker_name="w0"))
    h = stub.Heartbeat(pb.HeartbeatRequest(worker_id=r0.worker_id))
    assert h.learning_rate == 0.0
    servicer.set_learning_rate(5e-4)
    h2 = stub.Heartbeat(pb.HeartbeatRequest(worker_id=r0.worker_id))
    assert abs(h2.learning_rate - 5e-4) < 1e-12


def test_wait_when_drained(master_stack):
    stub, dispatcher, *_ = master_stack
    r = stub.RegisterWorker(pb.RegisterWorkerRequest(worker_name="w"))
    leases = []
    while True:
        resp = stub.GetTask(pb.GetTaskRequest(worker_id=r.worker_id))
        if resp.task.type != pb.TRAINING:
            break
        leases.append(resp.task)
    # all tasks leased but unreported → WAIT, not job_done
    assert resp.task.type == pb.WAIT and not resp.job_done
    assert resp.backoff_seconds > 0


# ---------------------------------------------------------------------- #
# master-generation fencing + idempotent re-registration (ISSUE 5)


@pytest.fixture()
def fenced_stack():
    """A generation-2 master (as if restarted once) over real gRPC."""
    dispatcher = TaskDispatcher(
        training_shards=[("t", 0, 40)], records_per_task=10, shuffle=False,
    )
    membership = Membership(heartbeat_timeout_s=30)
    membership.add_death_callback(dispatcher.recover_tasks)
    servicer = MasterServicer(dispatcher, membership, None, generation=2)
    server = make_server()
    add_master_servicer(server, servicer)
    port = server.add_insecure_port("[::]:0")
    server.start()
    stub = MasterStub(make_channel(f"localhost:{port}"))
    yield stub, dispatcher, membership, servicer
    server.stop(0)


def test_stale_generation_rpcs_are_fenced_retriably(fenced_stack):
    import grpc

    stub, dispatcher, membership, _ = fenced_stack
    r = stub.RegisterWorker(pb.RegisterWorkerRequest(worker_name="w"))
    stale = (("edl-master-generation", "1"),)
    for call, request in (
        (stub.GetTask, pb.GetTaskRequest(worker_id=r.worker_id)),
        (stub.ReportTaskResult,
         pb.ReportTaskResultRequest(worker_id=r.worker_id, task_id=1,
                                    success=True)),
        (stub.Heartbeat, pb.HeartbeatRequest(worker_id=r.worker_id)),
        (stub.RegisterWorker, pb.RegisterWorkerRequest(worker_name="w")),
    ):
        with pytest.raises(grpc.RpcError) as exc:
            call(request, metadata=stale)
        # FAILED_PRECONDITION naming the generation: the client-side
        # classifier (is_stale_generation) keys on exactly this
        assert exc.value.code() == grpc.StatusCode.FAILED_PRECONDITION
        assert "generation" in exc.value.details()
    # the fence sat in FRONT of every mutation: nothing leased, nothing
    # reported, no double join
    assert dispatcher.counts()["doing"] == 0
    assert dispatcher.counts()["finished_training"] == 0
    assert membership.alive_count() == 1


def test_current_generation_claim_and_no_claim_pass(fenced_stack):
    stub, dispatcher, *_ = fenced_stack
    r = stub.RegisterWorker(pb.RegisterWorkerRequest(worker_name="w"))
    # unfenced legacy caller (no claim) and a correct claim both serve
    resp = stub.GetTask(pb.GetTaskRequest(worker_id=r.worker_id))
    assert resp.task.type == pb.TRAINING
    resp2 = stub.GetTask(
        pb.GetTaskRequest(worker_id=r.worker_id),
        metadata=(("edl-master-generation", "2"),),
    )
    assert resp2.task.type == pb.TRAINING


def test_server_stamps_generation_on_trailing_metadata(fenced_stack):
    stub, *_ = fenced_stack
    _, call = stub.RegisterWorker.with_call(
        pb.RegisterWorkerRequest(worker_name="w")
    )
    trailing = dict(call.trailing_metadata() or ())
    assert trailing.get("edl-master-generation") == "2"


def test_reregister_is_idempotent_for_live_worker(fenced_stack):
    stub, dispatcher, membership, _ = fenced_stack
    r = stub.RegisterWorker(pb.RegisterWorkerRequest(worker_name="w"))
    v_before = membership.version
    # the reconnect handshake: generation-free, REREGISTER marker, same id
    r2 = stub.RegisterWorker(
        pb.RegisterWorkerRequest(
            worker_name="w", preferred_id_plus_one=r.worker_id + 1,
        ),
        metadata=(("edl-reregister", "1"),),
    )
    assert r2.worker_id == r.worker_id
    # no double join, no membership-version bump (the cohort must not
    # re-form for a control-plane-only reconnect)
    assert membership.alive_count() == 1
    assert membership.version == v_before
    assert r2.num_workers == 1


def test_reregister_revives_worker_reaped_during_outage(fenced_stack):
    stub, dispatcher, membership, _ = fenced_stack
    r = stub.RegisterWorker(pb.RegisterWorkerRequest(worker_name="w"))
    membership.mark_dead(r.worker_id, reason="missed heartbeats in outage")
    v_dead = membership.version
    r2 = stub.RegisterWorker(
        pb.RegisterWorkerRequest(
            worker_name="w", preferred_id_plus_one=r.worker_id + 1,
        ),
        metadata=(("edl-reregister", "1"),),
    )
    # revival IS a membership change: same id, version bumps once
    assert r2.worker_id == r.worker_id
    assert membership.version == v_dead + 1
    assert membership.alive_count() == 1
    # and the worker's heartbeat is accepted again (no shutdown order)
    h = stub.Heartbeat(pb.HeartbeatRequest(worker_id=r.worker_id))
    assert not h.shutdown


def test_reregister_of_unknown_id_falls_through_to_fresh_join(fenced_stack):
    stub, _, membership, _ = fenced_stack
    r = stub.RegisterWorker(
        pb.RegisterWorkerRequest(worker_name="w", preferred_id_plus_one=8),
        metadata=(("edl-reregister", "1"),),
    )
    # a journal-less master (or a truncated journal) still converges: the
    # unknown id becomes a fresh registration under that preferred id
    assert r.worker_id == 7
    assert membership.alive_count() == 1


# ---------------------------------------------------------------------- #
# batched leases + cohort-aggregated RPCs (ISSUE 8)


def test_get_task_max_tasks_batches_leases(master_stack):
    stub, dispatcher, *_ = master_stack
    r = stub.RegisterWorker(pb.RegisterWorkerRequest(worker_name="w"))
    resp = stub.GetTask(
        pb.GetTaskRequest(worker_id=r.worker_id, max_tasks=3)
    )
    assert len(resp.tasks) == 3
    # back-compat: the singular field mirrors the first lease
    assert resp.task.task_id == resp.tasks[0].task_id
    assert dispatcher.counts()["doing"] == 3
    # max_tasks unset (old worker) stays the classic single-lease shape
    resp1 = stub.GetTask(pb.GetTaskRequest(worker_id=r.worker_id))
    assert len(resp1.tasks) == 1
    assert resp1.task.type == pb.TRAINING


def test_get_task_max_tasks_is_capped_server_side(master_stack):
    stub, dispatcher, *_ = master_stack
    r = stub.RegisterWorker(pb.RegisterWorkerRequest(worker_name="w"))
    resp = stub.GetTask(
        pb.GetTaskRequest(worker_id=r.worker_id, max_tasks=10_000)
    )
    # 4 tasks exist (40 records / 10): all leased, none invented, and the
    # request's absurd batch did not fault the server
    assert len(resp.tasks) == 4
    from elasticdl_tpu.master.servicer import MasterServicer

    assert MasterServicer.MAX_LEASE_BATCH == 256


def test_register_with_member_names_and_coalesced_heartbeat(master_stack):
    stub, dispatcher, membership, *_ = master_stack
    r = stub.RegisterWorker(pb.RegisterWorkerRequest(
        worker_name="cohort", member_names=["cohort#p1", "cohort#p2"],
    ))
    assert len(r.member_ids) == 2
    assert r.num_workers == 1           # members are not logical workers
    from elasticdl_tpu.observability.health import encode_stats

    beat = pb.HeartbeatRequest(
        worker_id=r.worker_id,
        model_version=3,
        members=[
            pb.MemberBeat(
                worker_id=mid, model_version=3,
                stats_json=encode_stats(
                    {"step_p50_ms": 7.0, "phase": "train"}),
            )
            for mid in r.member_ids
        ],
    )
    resp = stub.Heartbeat(beat)
    assert not resp.shutdown
    recs = {h["worker_id"]: h for h in membership.health_snapshot()}
    for mid in r.member_ids:
        assert recs[mid]["step_p50_ms"] == 7.0
    # a garbage member payload degrades THAT member to liveness-only,
    # never the beat
    bad = pb.HeartbeatRequest(
        worker_id=r.worker_id,
        members=[pb.MemberBeat(worker_id=r.member_ids[0],
                               stats_json="}{not json")],
    )
    assert not stub.Heartbeat(bad).shutdown


def test_a_reaper_that_fires_on_a_live_worker_costs_no_task():
    """ROADMAP C21 over real gRPC, the worker's half being the one
    `MasterSession` both worker flavours hold: the reaper fires ONCE on a
    worker that is alive (its beats lapsed), its next beat is told to
    re-register, the handshake revives it, and the job ends with every task
    done exactly once — the lease the reaper requeued included."""
    import threading

    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.worker.session import MasterSession

    now = [0.0]
    dispatcher = TaskDispatcher(
        training_shards=[("t", 0, 40)], records_per_task=10, shuffle=False)
    membership = Membership(heartbeat_timeout_s=30, clock=lambda: now[0])
    membership.add_death_callback(dispatcher.recover_tasks)
    servicer = MasterServicer(dispatcher, membership, None, generation=2)
    server = make_server()
    add_master_servicer(server, servicer)
    port = server.add_insecure_port("[::]:0")
    server.start()
    shutdown = threading.Event()
    revived = []
    session = MasterSession(
        JobConfig(model_def="m.f", master_addr=f"localhost:{port}",
                  worker_heartbeat_s=0.01),
        shutdown, what="worker", when_lost="exiting EX_TEMPFAIL",
        on_reregistered=revived.append)
    try:
        wid = session.connect("w", -1).worker_id
        held = session.stub.GetTask(
            pb.GetTaskRequest(worker_id=wid), timeout=5).task
        now[0] = 31.0                       # three beats went missing
        assert membership.reap() == [wid] and membership.alive_count() == 0
        assert membership.reap() == []      # it fires once
        session.heartbeat_loop(
            model_version=lambda: 0, stats_payload=dict,
            on_response=lambda resp: shutdown.set())
        # one rejected beat, one handshake, one beat that was answered
        assert len(revived) == 1 and revived[0].worker_id == wid
        assert membership.alive_count() == 1 and not session.job_done
        done = []

        def report(task):
            accepted = session.stub.ReportTaskResult(
                pb.ReportTaskResultRequest(
                    worker_id=wid, task_id=task.task_id, success=True),
                timeout=5).accepted
            if accepted:
                done.append((task.start, task.end))

        report(held)        # its lease went back to the queue: not counted
        while True:
            resp = session.stub.GetTask(
                pb.GetTaskRequest(worker_id=wid), timeout=5)
            if resp.job_done:
                break
            report(resp.task)
        assert sorted(done) == [(0, 10), (10, 20), (20, 30), (30, 40)]
        counts = dispatcher.counts()
        assert counts["finished_training"] == 4, counts
        assert counts["todo"] == 0 and counts["doing"] == 0, counts
    finally:
        shutdown.set()
        session.close()
        server.stop(0)
