"""RetryingMasterStub: deadlines, idempotent-only retries, backoff with
jitter, circuit breaker, and fault-site wiring (proto/service.py)."""

import random

import grpc
import pytest

from elasticdl_tpu.common import faults
from elasticdl_tpu.proto import service
from elasticdl_tpu.proto.service import (
    DEFAULT_POLICIES,
    MasterStub,
    CircuitBreaker,
    MasterUnreachableError,
    RetryingMasterStub,
    RpcPolicy,
    rpc_site,
)


@pytest.fixture(autouse=True)
def _clean_injector():
    faults.reset()
    yield
    faults.reset()


class FakeRpcError(grpc.RpcError):
    def code(self):
        return grpc.StatusCode.UNAVAILABLE


class FakeStub:
    """Records (rpc, timeout) calls; fails the first `fail_first` of each."""

    def __init__(self, fail_first=0):
        self.calls = []
        self.fail_first = fail_first

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)

        def call(request, timeout=None):
            self.calls.append((name, timeout))
            if len(self.calls) <= self.fail_first:
                raise FakeRpcError()
            return f"{name}-ok"

        return call


def make_stub(fake, **kw):
    kw.setdefault("rng", random.Random(0))
    kw.setdefault("sleep", lambda s: None)
    return RetryingMasterStub(None, stub=fake, **kw)


def test_policy_classification_is_complete_and_conservative():
    # every RPC has a policy, and the mutating control-plane calls are
    # never auto-retried (see RpcPolicy docstring for the per-RPC why)
    assert set(DEFAULT_POLICIES) == set(service._RPCS)
    for name in ("RegisterWorker", "GetTask", "ReportTaskResult", "Heartbeat"):
        # Heartbeat is deliberately non-retryable: the servicer consumes
        # the one-shot should_checkpoint flag on read, so a retry after a
        # lost response would swallow a master-requested checkpoint
        assert not DEFAULT_POLICIES[name].idempotent
    for name in ("ReportEvaluationMetrics", "GetJobStatus"):
        assert DEFAULT_POLICIES[name].idempotent


def test_default_deadline_applied_and_explicit_timeout_wins():
    fake = FakeStub()
    stub = make_stub(fake)
    stub.GetTask("req")
    stub.GetTask("req", timeout=3.5)
    stub.Heartbeat("req")
    assert fake.calls == [
        ("GetTask", DEFAULT_POLICIES["GetTask"].timeout_s),
        ("GetTask", 3.5),
        ("Heartbeat", DEFAULT_POLICIES["Heartbeat"].timeout_s),
    ]


def test_idempotent_rpc_retries_until_success():
    fake = FakeStub(fail_first=2)
    stub = make_stub(fake)
    assert stub.GetJobStatus("req") == "GetJobStatus-ok"
    assert len(fake.calls) == 3      # 2 failures + 1 success


def test_non_idempotent_rpc_never_retries():
    fake = FakeStub(fail_first=1)
    stub = make_stub(fake)
    with pytest.raises(grpc.RpcError):
        stub.GetTask("req")
    assert len(fake.calls) == 1


def test_retries_exhausted_reraises_last_error():
    fake = FakeStub(fail_first=100)
    stub = make_stub(fake)
    with pytest.raises(FakeRpcError):
        stub.GetJobStatus("req")
    assert len(fake.calls) == DEFAULT_POLICIES["GetJobStatus"].max_attempts


def test_backoff_is_exponential_with_jitter_and_seed_deterministic():
    def run(seed):
        delays = []
        fake = FakeStub(fail_first=100)
        stub = make_stub(
            fake,
            rng=random.Random(seed),
            sleep=delays.append,
            policies={"Heartbeat": RpcPolicy(10.0, True, max_attempts=5)},
        )
        with pytest.raises(FakeRpcError):
            stub.Heartbeat("req")
        return delays

    a, b = run(7), run(7)
    assert a == b and len(a) == 4            # deterministic under one seed
    assert run(8) != a                        # jitter is real
    # each delay is bounded by the exponential cap base * 2^attempt
    for i, d in enumerate(a):
        assert 0 < d <= 0.2 * (2 ** i) + 1e-9


def test_on_success_hook_fires_on_every_successful_call():
    hits = []
    fake = FakeStub()
    stub = make_stub(fake, on_success=lambda: hits.append(1))
    stub.Heartbeat("req")
    stub.GetTask("req")
    assert len(hits) == 2


def test_circuit_opens_after_threshold_and_fails_fast():
    fake = FakeStub(fail_first=100)
    breaker = CircuitBreaker(failure_threshold=3, cooldown_s=60.0)
    stub = make_stub(fake, breaker=breaker)
    with pytest.raises(FakeRpcError):
        stub.GetJobStatus("req")              # 3 attempts = 3 failures
    assert breaker.is_open
    wire_calls = len(fake.calls)
    with pytest.raises(MasterUnreachableError):
        stub.GetTask("req")                   # no wire traffic while open
    assert len(fake.calls) == wire_calls


def test_half_open_probe_raising_non_retryable_does_not_latch_circuit():
    """A probe that dies with a NON-transport error (closed channel, bad
    request object) must still release the probe slot — otherwise the
    circuit stays open forever against a recovered master."""

    class WeirdStub:
        def __getattr__(self, name):
            def call(request, timeout=None):
                raise ValueError("Cannot invoke RPC on closed channel")

            return call

    breaker = CircuitBreaker(failure_threshold=1, cooldown_s=0.0)
    breaker.record_failure()                  # circuit opens
    assert breaker.is_open
    stub = make_stub(WeirdStub(), breaker=breaker)
    with pytest.raises(ValueError):
        stub.Heartbeat("req")                 # admitted as the probe, raises
    # the probe slot was released: the next call is admitted again
    assert breaker.allow()


def test_circuit_half_open_probe_recovers():
    fake = FakeStub(fail_first=3)
    breaker = CircuitBreaker(failure_threshold=3, cooldown_s=0.0)
    stub = make_stub(fake, breaker=breaker)
    with pytest.raises(FakeRpcError):
        stub.GetJobStatus("req")
    assert breaker.is_open
    # cooldown elapsed (0s): one probe is admitted and succeeds
    assert stub.GetJobStatus("req") == "GetJobStatus-ok"
    assert not breaker.is_open and breaker.consecutive_failures == 0


def test_send_fault_site_drops_call_before_the_wire():
    faults.install("rpc.get_task:drop@at=1")
    fake = FakeStub()
    stub = make_stub(fake)
    with pytest.raises(faults.FaultInjected):
        stub.GetTask("req")
    assert fake.calls == []                   # dropped before send
    assert stub.GetTask("req") == "GetTask-ok"


def test_recv_fault_site_loses_response_after_server_processed():
    faults.install("rpc.report_task_result.recv:drop@at=1")
    fake = FakeStub()
    stub = make_stub(fake)
    with pytest.raises(faults.FaultInjected):
        stub.ReportTaskResult("req")
    assert len(fake.calls) == 1               # the server DID see the call


def test_injected_drops_are_retried_for_idempotent_rpcs():
    faults.install("rpc.get_job_status:drop@at=1")
    fake = FakeStub()
    stub = make_stub(fake)
    assert stub.GetJobStatus("req") == "GetJobStatus-ok"
    assert len(fake.calls) == 1               # drop on attempt 1, retry hit wire


def test_rpc_site_naming():
    assert rpc_site("GetTask") == "rpc.get_task"
    assert rpc_site("ReportEvaluationMetrics") == "rpc.report_evaluation_metrics"
    assert rpc_site("Heartbeat") == "rpc.heartbeat"


# ---------------------------------------------------------------------- #
# master-generation handshake (ISSUE 5): breaker reset + stale-gen triage


class StaleGenError(grpc.RpcError):
    def code(self):
        return grpc.StatusCode.FAILED_PRECONDITION

    def details(self):
        return "stale master generation 1 (current 2); re-register to continue"


def test_is_stale_generation_classifier():
    from elasticdl_tpu.proto.service import is_stale_generation

    assert is_stale_generation(StaleGenError())
    assert not is_stale_generation(FakeRpcError())          # UNAVAILABLE
    assert not is_stale_generation(ValueError("generation"))

    class OtherPrecondition(grpc.RpcError):
        def code(self):
            return grpc.StatusCode.FAILED_PRECONDITION

        def details(self):
            return "some unrelated precondition"

    assert not is_stale_generation(OtherPrecondition())


def test_breaker_reset_clears_state_and_counts():
    breaker = CircuitBreaker(failure_threshold=2, cooldown_s=60.0)
    breaker.record_failure()
    breaker.record_failure()
    assert breaker.is_open
    before = service._BREAKER_RESETS.value()
    assert breaker.reset()
    assert not breaker.is_open and breaker.consecutive_failures == 0
    assert service._BREAKER_RESETS.value() == before + 1
    # idempotent: resetting a clean breaker reports nothing to clear
    assert not breaker.reset()
    assert service._BREAKER_RESETS.value() == before + 1


def test_stale_generation_fence_resets_breaker_and_raises_immediately():
    """A fenced call is an application answer on a healthy transport: it
    must clear the breaker (a restart's accumulated failures would hold
    the circuit open against a LIVE master forever) and surface without
    burning retries — the caller owns the re-register handshake."""

    class FencingStub:
        def __init__(self):
            self.calls = 0

        def GetTask(self, request, timeout=None):
            self.calls += 1
            raise StaleGenError()

    fake = FencingStub()
    breaker = CircuitBreaker(failure_threshold=5, cooldown_s=60.0)
    # the master was down for a while: failures accumulated
    breaker.record_failure()
    breaker.record_failure()
    stub = make_stub(fake, breaker=breaker)
    with pytest.raises(grpc.RpcError):
        stub.GetTask("req")
    assert fake.calls == 1                    # no retry burn on a fence
    assert breaker.consecutive_failures == 0  # handshake reset


def test_adopt_generation_from_trailing_metadata_resets_breaker():
    stub = make_stub(FakeStub())

    class Call:
        def __init__(self, md):
            self._md = md

        def trailing_metadata(self):
            return self._md

    stub._adopt_generation(Call((("edl-master-generation", "1"),)))
    assert stub.generation == 1
    # same generation again: no reset churn
    stub.breaker.record_failure()
    stub._adopt_generation(Call((("edl-master-generation", "1"),)))
    assert stub.breaker.consecutive_failures == 1
    # a CHANGED generation is the restart handshake landing
    stub._adopt_generation(Call((("edl-master-generation", "2"),)))
    assert stub.generation == 2
    assert stub.breaker.consecutive_failures == 0
    # garbage/absent trailing metadata is advisory, never fatal
    stub._adopt_generation(Call((("edl-master-generation", "bogus"),)))
    stub._adopt_generation(Call(()))
    assert stub.generation == 2


def test_channel_refresh_after_repeated_transport_failures():
    """The bounded reconnect loop: with a channel_factory wired, every
    `refresh_after` consecutive transport failures rebuilds the channel
    (fresh sockets — a subchannel stuck across a master restart must not
    be trusted forever), and a success resets the count."""

    class FakeChannel:
        def __init__(self, log):
            self.log = log
            self.closed = False

        def unary_unary(self, path, request_serializer=None,
                        response_deserializer=None):
            def mc(request, timeout=None, metadata=None):
                raise FakeRpcError()
            return mc

        def close(self):
            self.closed = True
            self.log.append("closed")

    built = []

    def factory():
        ch = FakeChannel(built)
        built.append(ch)
        return ch

    first = FakeChannel(built)
    stub = RetryingMasterStub(
        first,
        rng=random.Random(0),
        sleep=lambda s: None,
        breaker=CircuitBreaker(failure_threshold=100, cooldown_s=0.0),
        channel_factory=factory,
        refresh_after=3,
    )
    stub._last_refresh = -10.0                 # defeat the rate limit
    # Heartbeat is non-idempotent (1 attempt/call): three failing calls
    # make three consecutive transport failures -> one refresh
    for _ in range(3):
        with pytest.raises(grpc.RpcError):
            stub.Heartbeat("req")
    assert len([b for b in built if isinstance(b, FakeChannel)]) == 1
    # the old channel is dropped, NOT force-closed: close() cancels every
    # in-flight RPC, and the stub is shared across threads — a healthy
    # concurrent report racing the refresh must survive it
    assert not first.closed
    assert stub._channel is built[0]
    assert service._CHANNEL_REFRESHES.value() >= 1

    # a success resets the streak: the next lone failure does NOT refresh
    stub._stub = FakeStub()                    # next calls succeed
    stub.Heartbeat("req")
    assert stub._transport_failures == 0
    before = len([b for b in built if isinstance(b, FakeChannel)])
    stub._stub = MasterStub(built[0])          # failing channel again
    stub._last_refresh = -10.0
    with pytest.raises(grpc.RpcError):
        stub.Heartbeat("req")
    assert len([b for b in built if isinstance(b, FakeChannel)]) == before


def test_no_channel_factory_never_refreshes():
    fake = FakeStub(fail_first=2)
    stub = make_stub(fake)
    for _ in range(2):
        with pytest.raises(grpc.RpcError):
            stub.Heartbeat("req")
    stub.Heartbeat("req")                      # recovers without a factory
    assert stub._transport_failures == 0


# ---------------------------------------------------------------------- #
# shared registration handshake (worker.py and cohort.py both ride this)


class _RegisterStub:
    """Minimal stub surface register_with_retry needs: RegisterWorker +
    a mutable generation claim. Scripted failures, then success."""

    def __init__(self, fail_first=0, errors=None):
        self.generation = 7
        self.calls = []                 # (preferred_id_plus_one, metadata)
        self._errors = list(errors or [])
        self._fail_first = fail_first

    def RegisterWorker(self, request, timeout=None, metadata=None):
        self.calls.append((request.preferred_id_plus_one, metadata))
        if self._errors:
            raise self._errors.pop(0)
        if len(self.calls) <= self._fail_first:
            raise FakeRpcError()
        return "registered"


@pytest.fixture
def _fast_backoff(monkeypatch):
    monkeypatch.setattr(service.random, "uniform", lambda a, b: 0.0)


def test_register_with_retry_retries_carry_reregister_marker(_fast_backoff):
    import threading

    stub = _RegisterStub(fail_first=2)
    resp = service.register_with_retry(
        stub, name="w", preferred_id=3, window_s=60.0,
        shutdown=threading.Event(),
    )
    assert resp == "registered"
    # initial attempt is a plain join; retries with a known id carry the
    # idempotent-reconnect marker so the master never allocates a ghost id
    assert stub.calls[0] == (4, None)
    assert stub.calls[1:] == [(4, ((service.REREGISTER_KEY, "1"),))] * 2


def test_register_with_retry_fresh_join_never_carries_marker(_fast_backoff):
    import threading

    stub = _RegisterStub(fail_first=1)
    service.register_with_retry(
        stub, name="w", preferred_id=-1, window_s=60.0,
        shutdown=threading.Event(),
    )
    assert stub.calls == [(0, None), (0, None)]


def test_register_with_retry_window_zero_disables_deadline(
    _fast_backoff, monkeypatch
):
    """config.py documents master_unreachable_timeout_s=0 as 'disables':
    registration must retry indefinitely (until shutdown), not fall back
    to a hidden 60s boot deadline."""
    import threading

    stub = _RegisterStub(fail_first=4)
    clock = [0.0]

    def far_future():
        clock[0] += 1e6                 # any hidden deadline would expire
        return clock[0]

    monkeypatch.setattr(service.time, "monotonic", far_future)
    resp = service.register_with_retry(
        stub, name="w", preferred_id=0, window_s=0.0,
        shutdown=threading.Event(),
    )
    assert resp == "registered"


def test_register_with_retry_deadline_expiry_reraises(
    _fast_backoff, monkeypatch
):
    import threading

    stub = _RegisterStub(fail_first=100)
    clock = [0.0]

    def ticking():
        clock[0] += 10.0
        return clock[0]

    monkeypatch.setattr(service.time, "monotonic", ticking)
    with pytest.raises(FakeRpcError):
        service.register_with_retry(
            stub, name="w", preferred_id=0, window_s=15.0,
            shutdown=threading.Event(),
        )


def test_register_with_retry_stale_generation_clears_claim(_fast_backoff):
    import threading

    stub = _RegisterStub(errors=[StaleGenError()])
    resp = service.register_with_retry(
        stub, name="w", preferred_id=0, window_s=60.0,
        shutdown=threading.Event(),
    )
    assert resp == "registered"
    # the stale claim was dropped so the retry adopted the successor's
    # generation from its own handshake
    assert stub.generation is None


def test_reregister_uses_existing_id_and_marker():
    stub = _RegisterStub()
    resp = service.reregister(stub, name="w", worker_id=6)
    assert resp == "registered"
    assert stub.generation is None      # claim cleared BEFORE the call
    assert stub.calls == [(7, ((service.REREGISTER_KEY, "1"),))]
