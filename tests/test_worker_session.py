"""worker/session.py — a worker process's session with the master, against a
fake stub: no server, no jax, no sleeping (beats are 1 ms apart).

What differs between the two owners (`Worker`, the cohort's leader) comes in
as arguments; the cases below drive both shapes through the one class.
"""

import threading

import grpc
import pytest

from elasticdl_tpu.common import faults
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.proto.service import REREGISTER_KEY
from elasticdl_tpu.worker import session as session_mod
from elasticdl_tpu.worker.session import MasterSession
from tests.conftest import listening


class StaleGeneration(grpc.RpcError):
    """What the servicer's fence raises on the client side."""

    def code(self):
        return grpc.StatusCode.FAILED_PRECONDITION

    def details(self):
        return "stale master generation 1 (current 2); re-register to continue"


class FakeStub:
    """Scripted master: `beats` holds, per heartbeat, a response or an
    exception to raise. When the script runs out, the shutdown event is set
    so the loop ends."""

    def __init__(self, shutdown, beats=()):
        self.shutdown = shutdown
        self.beats = list(beats)
        self.registers = []      # (request, metadata)
        self.heartbeats = []     # (request, timeout, metadata)
        self.generation = 7
        self.breaker = type("B", (), {"is_open": False})()

    def RegisterWorker(self, request, timeout=None, metadata=None):
        self.registers.append((request, metadata))
        wid = request.preferred_id_plus_one - 1
        return pb.RegisterWorkerResponse(
            worker_id=wid if wid >= 0 else 3, membership_version=5,
            num_workers=2,
            member_ids=range(10, 10 + len(request.member_names)),
        )

    def Heartbeat(self, request, timeout=None, metadata=None):
        self.heartbeats.append((request, timeout, metadata))
        step = self.beats.pop(0) if self.beats else pb.HeartbeatResponse()
        if not self.beats:
            self.shutdown.set()
        if isinstance(step, BaseException):
            raise step
        return step


def make_session(beats=(), what="worker", **cfg):
    cfg.setdefault("worker_heartbeat_s", 0.001)
    shutdown = threading.Event()
    reregistered = []
    s = MasterSession(
        JobConfig(model_def="m.f", master_addr="localhost:1", **cfg),
        shutdown, what=what, when_lost="exiting EX_TEMPFAIL",
        on_reregistered=reregistered.append,
    )
    s.stub = FakeStub(shutdown, beats)
    s.worker_id = 3
    s.name = "host:1"
    return s, shutdown, reregistered


def beat(s, **kw):
    """Run the loop on this thread until the script ends; returns the
    responses the owner's hook saw."""
    seen = []
    kw.setdefault("model_version", lambda: 11)
    kw.setdefault("stats_payload", lambda: {"steps": 1})
    s.heartbeat_loop(on_response=seen.append, **kw)
    return seen


@pytest.mark.parametrize("owner,preferred,fields", [
    ("worker", -1, {"data_addr": "localhost:999"}),
    ("cohort leader", 0, {"member_names": ["c#p1", "c#p2"]}),
])
def test_connect_passes_each_owner_s_register_fields(
        monkeypatch, owner, preferred, fields):
    shutdown = threading.Event()
    built = {}

    def fake_stub(channel, on_success, channel_factory):
        built.update(channel=channel, on_success=on_success,
                     factory=channel_factory)
        return FakeStub(shutdown)

    monkeypatch.setattr(session_mod, "make_channel", lambda addr: ("ch", addr))
    monkeypatch.setattr(session_mod, "RetryingMasterStub", fake_stub)
    s = MasterSession(
        JobConfig(model_def="m.f", master_addr="localhost:1"), shutdown,
        what=owner, when_lost="", on_reregistered=lambda resp: None)
    resp = s.connect("name:1", preferred, **fields)
    (req, md), = s.stub.registers
    assert req.worker_name == "name:1" and md is None
    assert req.preferred_id_plus_one == preferred + 1
    assert req.data_plane_addr == fields.get("data_addr", "")
    assert list(req.member_names) == fields.get("member_names", [])
    assert s.worker_id == resp.worker_id == (3 if preferred < 0 else 0)
    assert len(resp.member_ids) == len(fields.get("member_names", []))
    # the stub's success hook is the unreachable clock, and a rebuilt
    # channel goes to the same address
    assert built["channel"] == built["factory"]() == ("ch", "localhost:1")
    s.last_master_ok = 0.0
    built["on_success"]()
    assert s.last_master_ok > 0.0
    # the same fields, name and id ride every later reconnect handshake
    s.reregister()
    req2, md2 = s.stub.registers[1]
    assert md2 == ((REREGISTER_KEY, "1"),)
    assert req2.worker_name == "name:1"
    assert req2.preferred_id_plus_one == s.worker_id + 1
    assert req2.data_plane_addr == req.data_plane_addr
    assert list(req2.member_names) == list(req.member_names)


def test_a_stale_generation_is_answered_by_a_re_register_and_the_loop_goes_on():
    s, shutdown, reregistered = make_session(
        [StaleGeneration(), pb.HeartbeatResponse(num_workers=2)])
    s.stub.generation = 1
    seen = beat(s)
    # the handshake cleared the claim, re-registered under the SAME id with
    # the marker, and handed the response to the owner
    (req, md), = s.stub.registers
    assert md == ((REREGISTER_KEY, "1"),)
    assert req.preferred_id_plus_one == 4 and req.worker_name == "host:1"
    assert s.stub.generation is None
    assert [r.worker_id for r in reregistered] == [3]
    # the beat after it went out as usual; nobody was written off
    assert len(s.stub.heartbeats) == 2 and len(seen) == 1
    assert not s.master_lost


def test_before_registration_nothing_is_re_registered():
    s, *_ = make_session()
    s.worker_id = -1
    assert s.maybe_reconnect(StaleGeneration()) is False
    assert s.stub.registers == []


def test_a_failed_handshake_is_not_a_reconnect():
    s, _, reregistered = make_session()
    s.stub.RegisterWorker = lambda *a, **k: (_ for _ in ()).throw(
        ConnectionError("master crashed again"))
    assert s.maybe_reconnect(StaleGeneration()) is False
    assert reregistered == []


def test_any_other_error_runs_the_unreachable_clock_and_shuts_down_once(caplog):
    s, shutdown, _ = make_session(
        [ConnectionError("refused")] * 3, master_unreachable_timeout_s=5.0)
    lost = []
    real = s.master_unreachable

    def unreachable():
        # the master has answered recently at the first failure, and has
        # been silent for longer than the limit from the second on
        if len(s.stub.heartbeats) >= 2:
            s.last_master_ok -= 6.0
        lost.append(real())
        return lost[-1]

    s.master_unreachable = unreachable
    with listening(caplog, session_mod.logger.name):
        beat(s)
        # asked again (the lease path does): still lost, and said ONCE
        assert real() is True
    assert lost[0] is False and lost[1] is True
    # shutdown set by the session at the second failure: the third never ran
    assert len(s.stub.heartbeats) == 2
    assert s.master_lost and shutdown.is_set()
    said = [r for r in caplog.records if "master presumed gone" in r.message]
    assert len(said) == 1
    assert said[0].getMessage().endswith("exiting EX_TEMPFAIL")


def test_an_unreachable_clock_of_zero_never_gives_up():
    s, shutdown, _ = make_session(master_unreachable_timeout_s=0.0)
    s.last_master_ok -= 1e6
    assert s.master_unreachable() is False
    assert not s.master_lost and not shutdown.is_set()


def test_a_stats_payload_that_raises_still_beats():
    def payload():
        raise RuntimeError("profiler hiccup")

    s, *_ = make_session([pb.HeartbeatResponse()])
    seen = beat(s, stats_payload=payload)
    (req, timeout, md), = s.stub.heartbeats
    assert md is None and timeout == 10          # liveness only, same deadline
    assert req.worker_id == 3 and req.model_version == 11
    assert len(seen) == 1


def test_stats_ride_as_metadata_and_the_owner_s_fields_in_the_request():
    s, *_ = make_session([pb.HeartbeatResponse()], what="cohort leader")
    members = [pb.MemberBeat(worker_id=10, model_version=4)]
    beat(s, request_fields=lambda: {"members": members})
    (req, _, md), = s.stub.heartbeats
    assert [m.worker_id for m in req.members] == [10]
    (key, value), = md
    assert key == session_mod.STATS_METADATA_KEY and '"steps":1' in value


@pytest.mark.parametrize("job_done", [True, False])
def test_a_shutdown_ends_the_session_with_and_without_job_done(job_done):
    s, shutdown, _ = make_session([
        pb.HeartbeatResponse(shutdown=True, job_done=job_done,
                             should_checkpoint=True),
        pb.HeartbeatResponse(),
    ])
    seen = beat(s)
    # the loop ended there: the second beat never went out, and a response
    # that ends the session is not the owner's to act on
    assert len(s.stub.heartbeats) == 1 and seen == []
    assert shutdown.is_set() and s.job_done is job_done
    assert not s.checkpoint_requested and not s.master_lost


def test_a_checkpoint_request_is_kept_for_the_owner_s_next_task_boundary():
    s, *_ = make_session([
        pb.HeartbeatResponse(should_checkpoint=True, learning_rate=0.5),
        pb.HeartbeatResponse(),
    ])
    seen = beat(s)
    assert s.checkpoint_requested and not s.job_done
    assert [r.learning_rate for r in seen] == [0.5, 0.0]


@pytest.mark.parametrize("given", ["worker.heartbeat", None])
def test_the_fault_point_fires_only_when_given(caplog, given):
    s, *_ = make_session([pb.HeartbeatResponse(), pb.HeartbeatResponse()])
    faults.install("worker.heartbeat:drop@at=1")
    try:
        with listening(caplog, session_mod.logger.name):
            beat(s, fault_point=given)
    finally:
        faults.reset()
    # a dropped beat falls through the same triage as a network failure: no
    # re-register, and the beats after it go out
    dropped = [r for r in caplog.records if "heartbeat failed" in r.message]
    assert len(dropped) == (1 if given else 0)
    assert len(s.stub.heartbeats) == 2 and s.stub.registers == []


def test_ride_alongs_carry_the_breaker_and_survive_a_tier_hiccup():
    class Tier:
        class client:
            @staticmethod
            def tier_stats():
                raise RuntimeError("tier hiccup")

    s, *_ = make_session()
    s.stub.breaker.is_open = True
    stats = s.stats_ride_alongs(Tier())
    assert stats["breaker_open"] == 1 and "world_version" in stats
    s.stub = None                       # before connect()
    assert s.stats_ride_alongs()["breaker_open"] == 0


def test_close_joins_the_heartbeat_thread_and_closes_the_channel():
    class Channel:
        closed = False

        def close(self):
            self.closed = True

    s, shutdown, _ = make_session([pb.HeartbeatResponse()] * 1000,
                                  worker_heartbeat_s=0.01)
    s._channel = Channel()
    seen = []
    s.start_heartbeats(
        model_version=lambda: 0, stats_payload=dict,
        on_response=seen.append)
    while not seen:
        pass
    shutdown.set()
    s.close()
    assert not s._heartbeat_thread.is_alive() and s._channel.closed
