"""Partition-tolerant gRPC embedding data plane (ISSUE 15).

Covers the wire (codec, end-to-end equivalence with LocalTransport,
error mapping, deadline propagation), the robustness layer (deadline
budgets, per-owner breakers + channel refresh, hedged reads, the
degraded-mode ladder), the push queue (bounded, journaled, in-order
drain, replay identity), the exactly-once fence under response-side
(.recv) fault drops over the REAL transport, and the owner address
book (registration -> shard-map response -> journal replay).

Everything runs host-mode stores on loopback gRPC — no jax, no
subprocesses; fast enough for tier-1.
"""

import socket
import time

import numpy as np
import pytest

from elasticdl_tpu.common import faults
from elasticdl_tpu.embedding import data_plane as dp
from elasticdl_tpu.embedding import sharding, tier
from elasticdl_tpu.embedding.store import (
    EmbeddingShardStore,
    StaleShardMapError,
)
from elasticdl_tpu.embedding.transport import (
    DEGRADED_READS,
    LocalTransport,
    OwnerUnavailableError,
    SimWireTransport,
)

SPEC = sharding.TableSpec("users", vocab=4096, dim=8, seed=3)


def make_view(num_shards=2, owners=(0, 0), replicas=((1,), (1,)),
              version=1):
    return sharding.ShardMapView(
        version=version, num_shards=num_shards, owners=tuple(owners),
        tables=(SPEC,), replicas=tuple(tuple(r) for r in replicas),
    )


@pytest.fixture()
def served_pair():
    """(primary store+server, replica store+server, addrs) — owner 0
    primary for both shards, owner 1 holding synced replica copies."""
    view = make_view()
    st0 = EmbeddingShardStore(0, device=False)
    st0.attach(view)
    st0.set_delta_logging(True)
    srv0 = dp.EmbeddingDataServer(st0)
    p0 = srv0.start()
    st1 = EmbeddingShardStore(1, device=False)
    st1.attach(view)
    srv1 = dp.EmbeddingDataServer(st1)
    p1 = srv1.start()
    peer = dp.GrpcTransport({0: f"127.0.0.1:{p0}"})
    for s in range(view.num_shards):
        st1.sync_replica_from(peer, 0, "users", s)
    yield {
        "view": view, "st0": st0, "st1": st1,
        "addr0": f"127.0.0.1:{p0}", "addr1": f"127.0.0.1:{p1}",
        "sync": lambda: [st1.sync_replica_from(peer, 0, "users", s)
                         for s in range(view.num_shards)],
    }
    srv0.stop()
    srv1.stop()
    peer.close()


@pytest.fixture()
def blackhole():
    """A listener that accepts and never answers — the worst partition
    shape (connects succeed, every call hangs to its deadline)."""
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    sock.listen(16)
    yield f"127.0.0.1:{sock.getsockname()[1]}"
    sock.close()


# ------------------------------------------------------------------ #
# wire


def test_codec_round_trip():
    ids = np.array([3, -1, 7, 4095], np.int32)
    assert np.array_equal(dp.ids_from_bytes(dp.ids_to_bytes(ids)), ids)
    rows = np.arange(12, dtype=np.float32).reshape(3, 4)
    out = dp.rows_from_bytes(dp.rows_to_bytes(rows), 4)
    assert np.array_equal(out, rows)


def test_grpc_end_to_end_matches_local(served_pair):
    pair = served_pair
    tr = dp.GrpcTransport({0: pair["addr0"]})
    local = LocalTransport()
    local.register(pair["st0"])
    ids = np.array([0, 2, 4, -1], np.int32)
    rows_g, wm_g = tr.pull(0, "users", 0, ids, map_version=1,
                           with_watermark=True)
    rows_l, wm_l = local.pull(0, "users", 0, ids, map_version=1,
                              with_watermark=True)
    assert wm_g == wm_l and np.allclose(rows_g, rows_l)
    assert np.all(rows_g[3] == 0.0)   # sentinel row zeroed over the wire

    g = np.ones((4, 8), np.float32)
    ack_g = tr.push(0, "users", 0, ids, g, client_id="cg", seq=1,
                    map_version=1, with_watermark=True)
    assert ack_g[0] is True
    # duplicate seq: fence holds over the wire, watermark still returns
    dup = tr.push(0, "users", 0, ids, g, client_id="cg", seq=1,
                  map_version=1, with_watermark=True)
    assert dup == (False, ack_g[1])

    payload_g = tr.fetch_shard(0, "users", 0)
    payload_l = local.fetch_shard(0, "users", 0)
    assert np.allclose(payload_g["rows"], payload_l["rows"])
    assert payload_g["applied"] == payload_l["applied"]
    assert payload_g["wm"] == payload_l["wm"]
    assert (tr.shard_watermark(0, "users", 0)
            == local.shard_watermark(0, "users", 0))
    delta_g = tr.fetch_delta(0, "users", 0, 0)
    delta_l = local.fetch_delta(0, "users", 0, 0)
    assert delta_g["wm"] == delta_l["wm"]
    assert len(delta_g["entries"]) == len(delta_l["entries"])
    e_g, e_l = delta_g["entries"][0], delta_l["entries"][0]
    assert e_g["seq"] == e_l["seq"] and e_g["client_id"] == e_l["client_id"]
    assert np.allclose(e_g["rows"], e_l["rows"])
    # too-far-back delta: None on both transports
    assert tr.fetch_delta(0, "users", 0, -5) is None
    tr.close()


def test_grpc_errors_map_to_tier_vocabulary(served_pair, blackhole):
    pair = served_pair
    tr = dp.GrpcTransport({0: pair["addr0"], 9: blackhole})
    ids = np.arange(4, dtype=np.int32)
    with pytest.raises(StaleShardMapError):
        tr.pull(0, "users", 0, ids, map_version=99, with_watermark=True)
    with pytest.raises(OwnerUnavailableError):
        tr.pull(7, "users", 0, ids)          # no address at all
    t0 = time.perf_counter()
    with pytest.raises(dp.DeadlineExceededError):
        tr.pull(9, "users", 0, ids, map_version=1, timeout_s=0.2)
    assert 0.15 <= time.perf_counter() - t0 < 2.0
    tr.close()


def test_replica_pull_and_watermark_over_grpc(served_pair):
    pair = served_pair
    tr = dp.GrpcTransport({1: pair["addr1"]})
    ids = np.arange(4, dtype=np.int32)
    rows, wm = tr.pull(1, "users", 0, ids, map_version=1,
                       with_watermark=True, replica=True)
    assert rows.shape == (4, 8)
    assert tr.shard_watermark(1, "users", 0, replica=True) == wm
    # a replica store rejects pushes as stale-map over the wire too
    with pytest.raises(StaleShardMapError):
        tr.push(1, "users", 0, ids, np.ones((4, 8), np.float32),
                client_id="c", seq=1, map_version=1)
    tr.close()


# ------------------------------------------------------------------ #
# response-side fault sites + exactly-once over the real wire


def test_recv_fault_sites_exist_on_local_transport():
    st = EmbeddingShardStore(0, device=False)
    st.attach(make_view(replicas=((), ())))
    local = LocalTransport()
    local.register(st)
    ids = np.arange(4, dtype=np.int32)
    inj = faults.install("emb.pull.recv:drop@at=1")
    try:
        with pytest.raises(faults.FaultInjected):
            local.pull(0, "users", 0, ids, map_version=1,
                       with_watermark=True)
        # the owner DID serve before the reply was lost
        assert inj.hits("emb.pull.recv") == 1
        local.pull(0, "users", 0, ids, map_version=1, with_watermark=True)
    finally:
        faults.uninstall()
    inj = faults.install("emb.fetch_delta.recv:drop@at=1")
    try:
        with pytest.raises(faults.FaultInjected):
            local.fetch_delta(0, "users", 0, 0)
        assert inj.hits("emb.fetch_delta.recv") == 1
    finally:
        faults.uninstall()


def test_lost_push_ack_over_grpc_absorbed_by_seq_fence(served_pair):
    """The PR 10 lost-ack test covered LocalTransport only; this pins
    the same contract over the REAL transport: a push whose reply is
    dropped AFTER the owner applied re-sends under the same seq through
    the robustness layer, and the store's fence turns the duplicate
    into an ack with no second apply."""
    pair = served_pair
    res = dp.ResilientTransport(
        dp.GrpcTransport({0: pair["addr0"]}),
        policies={"push": dp.CallPolicy(budget_s=2.0, max_attempts=3)},
        queue_max=0, backoff_base_s=0.001,
    )
    ids = np.arange(4, dtype=np.int32)
    g = np.ones((4, 8), np.float32)
    before = np.array(pair["st0"].pull("users", 0, ids))
    faults.install("emb.push.recv:drop@at=1")
    try:
        applied, wm = res.push(0, "users", 0, ids, g, client_id="lost",
                               seq=1, map_version=1, with_watermark=True)
    finally:
        faults.uninstall()
    # the retried send was deduped: applied=False is the duplicate ack
    assert applied is False
    after = np.array(pair["st0"].pull("users", 0, ids))
    assert np.allclose(after - before, g)      # exactly once, not twice
    res.close()


# ------------------------------------------------------------------ #
# robustness layer: budgets, breakers, hedging, degraded ladder


def test_deadline_budget_bounds_the_whole_call(blackhole):
    res = dp.ResilientTransport(
        dp.GrpcTransport({0: blackhole}),
        policies={"pull": dp.CallPolicy(budget_s=0.4, max_attempts=3)},
        hedge=False, queue_max=0,
    )
    ids = np.arange(4, dtype=np.int32)
    t0 = time.perf_counter()
    with pytest.raises(OwnerUnavailableError):
        res.pull(0, "users", 0, ids, map_version=1, with_watermark=True)
    wall = time.perf_counter() - t0
    # retries SPLIT the budget; they never extend it
    assert wall < 1.5, wall
    res.close()


def test_breaker_opens_fails_fast_and_refreshes_channel(blackhole):
    refreshed = []
    inner = dp.GrpcTransport({0: blackhole})
    orig = inner.refresh_channel
    inner.refresh_channel = lambda owner: (refreshed.append(owner),
                                           orig(owner))
    res = dp.ResilientTransport(
        inner,
        policies={"pull": dp.CallPolicy(budget_s=0.15, max_attempts=1)},
        hedge=False, queue_max=0, breaker_failures=2,
        breaker_cooldown_s=30.0, refresh_after=2,
    )
    from elasticdl_tpu.proto import service as proto_service

    master_open0 = proto_service._BREAKER_OPEN.value()
    master_trips0 = proto_service._BREAKER_TRIPS.value()
    ids = np.arange(4, dtype=np.int32)
    for _ in range(2):
        with pytest.raises(OwnerUnavailableError):
            res.pull(0, "users", 0, ids, map_version=1)
    assert res.owner_degraded(0)
    assert refreshed == [0]       # wedge recovery kicked in
    # the per-owner breaker must NOT read as a master outage: the
    # inherited CircuitBreaker runs telemetry-free for the data plane
    assert proto_service._BREAKER_OPEN.value() == master_open0
    assert proto_service._BREAKER_TRIPS.value() == master_trips0
    t0 = time.perf_counter()
    with pytest.raises(OwnerUnavailableError):
        res.pull(0, "users", 0, ids, map_version=1)
    # breaker open -> fail fast, not another 150 ms wire wait
    assert time.perf_counter() - t0 < 0.1
    res.close()


def test_hedged_read_serves_from_replica_when_primary_partitions(
        served_pair, blackhole):
    pair = served_pair
    res = dp.ResilientTransport(
        dp.GrpcTransport({0: pair["addr0"], 1: pair["addr1"]}),
        policies={"pull": dp.CallPolicy(budget_s=1.0, max_attempts=2)},
        staleness_bound=4, view_fn=lambda: pair["view"],
        hedge_delay_ms=5.0, queue_max=0, breaker_cooldown_s=30.0,
    )
    ids = np.arange(4, dtype=np.int32)
    healthy, wm0 = res.pull(0, "users", 0, ids, map_version=1,
                            with_watermark=True)
    deg0 = DEGRADED_READS.value(mode="replica")
    res.update_addresses({0: blackhole})
    t0 = time.perf_counter()
    rows, wm = res.pull(0, "users", 0, ids, map_version=1,
                        with_watermark=True)
    wall = time.perf_counter() - t0
    assert np.allclose(rows, healthy) and wm == wm0
    assert wall < 0.5, wall       # hedge delay + replica rtt, not budget
    assert DEGRADED_READS.value(mode="replica") > deg0
    res.close()


def test_hedged_read_refuses_stale_replica(served_pair, blackhole):
    """Credibility: a replica further behind than the staleness bound
    must NOT win the hedge — a partition is not a license to serve
    arbitrarily stale rows (the degraded ladder's 'block' rung)."""
    pair = served_pair
    res = dp.ResilientTransport(
        dp.GrpcTransport({0: pair["addr0"], 1: pair["addr1"]}),
        policies={"pull": dp.CallPolicy(budget_s=0.4, max_attempts=2)},
        staleness_bound=1, view_fn=lambda: pair["view"],
        hedge_delay_ms=5.0, queue_max=0,
    )
    ids = np.arange(4, dtype=np.int32)
    # advance the primary past the replica's sync point by > bound
    for seq in (1, 2, 3):
        res.push(0, "users", 0, ids, np.ones((4, 8), np.float32),
                 client_id="w", seq=seq, map_version=1,
                 with_watermark=True)
    assert res.observed_wm("users", 0) >= 3
    blocked0 = DEGRADED_READS.value(mode="blocked")
    res.update_addresses({0: blackhole})
    with pytest.raises(OwnerUnavailableError):
        res.pull(0, "users", 0, ids, map_version=1, with_watermark=True)
    assert DEGRADED_READS.value(mode="blocked") > blocked0
    # after the replica catches up, the same read serves
    pair["sync"]()
    rows, wm = res.pull(0, "users", 0, ids, map_version=1,
                        with_watermark=True)
    assert wm >= 3
    res.close()


# ------------------------------------------------------------------ #
# degraded cache rung + the staleness contract (satellite)


def _degraded(res, owner, within_s=5.0):
    """`res.owner_degraded(owner)`, polled: the breaker opens when the read
    loses its hedge race OR when the abandoned primary call reaches its own
    wire deadline, and an earlier read's slow primary answering late closes
    it in between."""
    deadline = time.monotonic() + within_s
    while not res.owner_degraded(owner) and time.monotonic() < deadline:
        time.sleep(0.02)
    return res.owner_degraded(owner)


def _reader_client(pair, blackhole_addr=None, staleness=2):
    res = dp.ResilientTransport(
        dp.GrpcTransport({0: pair["addr0"], 1: pair["addr1"]}),
        policies={
            "pull": dp.CallPolicy(budget_s=0.6, max_attempts=2),
            "watermark": dp.CallPolicy(budget_s=0.3, max_attempts=1),
        },
        staleness_bound=staleness, view_fn=lambda: pair["view"],
        hedge_delay_ms=5.0, queue_max=0, breaker_failures=1,
        breaker_cooldown_s=30.0,
    )
    client = tier.EmbeddingTierClient(
        lambda: pair["view"], res, client_id="reader",
        cache_rows=512, cache_staleness=staleness,
        max_retries=2, retry_backoff_s=0.01,
    )
    client.wm_probe_every = 1
    return res, client


def test_degraded_cache_hits_are_attributed(served_pair, blackhole):
    pair = served_pair
    res, client = _reader_client(pair)
    ids = np.array([1, 3, 5, 7], np.int64)
    warm = client.pull("users", ids)               # cache warms
    res.update_addresses({0: blackhole})
    # open the breaker: one failed/hedged read condemns the primary
    client.pull("users", ids + 2)
    assert _degraded(res, 0)
    cache0 = DEGRADED_READS.value(mode="cache")
    again = client.pull("users", ids)              # pure cache hits
    assert np.allclose(again, warm)
    assert DEGRADED_READS.value(mode="cache") > cache0
    client.close()
    res.close()


def test_staleness_bound_honored_during_partition_with_foreign_pushes(
        served_pair, blackhole):
    """THE contract test (satellite): reader partitioned from the
    primary, a foreign writer keeps pushing. The reader's cached row
    must never be served once the owner is more than the staleness
    bound past it — the replica-probe fallback is what keeps the bound
    enforceable, and the read must come back FRESH (via the replica),
    not stale-from-cache."""
    pair = served_pair
    staleness = 2
    res, client = _reader_client(pair, staleness=staleness)
    ids = np.array([4, 6], np.int64)               # shard 0 rows
    stale_rows = client.pull("users", ids)         # cached at wm=0
    # partition the reader from the primary
    res.update_addresses({0: blackhole})
    client.pull("users", np.array([8, 10], np.int64))  # trips the breaker
    assert _degraded(res, 0)
    # foreign writer pushes K > staleness bound to the REAL primary
    writer = dp.GrpcTransport({0: pair["addr0"]})
    delta = np.ones((2, 8), np.float32)
    for seq in (1, 2, 3):
        writer.push(0, "users", 0,
                    np.array([2, 3], np.int32),     # local rows of 4, 6
                    delta, client_id="foreign", seq=seq, map_version=1,
                    with_watermark=True)
    pair["sync"]()                                  # replica catches up
    # the reader's next lookups: a full-hit read first probes (primary
    # dead -> REPLICA watermark = 3 > 0 + staleness) — the stale row
    # must evict and the re-fetch must carry the foreign pushes
    fresh = None
    for _ in range(4):          # probe cadence is per full-hit lookup
        fresh = client.pull("users", ids)
    assert np.allclose(fresh, stale_rows + 3 * delta), (
        "reader served a row beyond the staleness bound during the "
        "partition")
    client.close()
    res.close()
    writer.close()


# ------------------------------------------------------------------ #
# push queue: bounded, journaled, in-order drain


def test_push_queue_bounded_and_replays_in_order(served_pair, blackhole,
                                                 tmp_path):
    pair = served_pair
    journal = str(tmp_path / "pq.jsonl")
    res = dp.ResilientTransport(
        dp.GrpcTransport({0: pair["addr0"]}),
        policies={"push": dp.CallPolicy(budget_s=0.2, max_attempts=1)},
        hedge=False, queue_journal=journal, queue_max=3,
        breaker_failures=1, breaker_cooldown_s=0.2,
    )
    ids = np.arange(4, dtype=np.int32)
    g = np.ones((4, 8), np.float32)
    before = np.array(pair["st0"].pull("users", 0, ids))
    res.update_addresses({0: blackhole})
    for seq in (1, 2, 3):
        ack = res.push(0, "users", 0, ids, g * seq, client_id="q",
                       seq=seq, map_version=1, with_watermark=True)
        assert ack[0] is False     # parked, honestly not-applied
    assert res.queue.depth(0) == 3
    # bounded: the 4th push is refused, never silently buffered
    with pytest.raises(OwnerUnavailableError):
        res.push(0, "users", 0, ids, g, client_id="q", seq=4,
                 map_version=1, with_watermark=True)
    # heal -> cooldown -> a NEW push drains the backlog first (order
    # fence), then applies itself
    res.update_addresses({0: pair["addr0"]})
    time.sleep(0.25)
    applied, wm = res.push(0, "users", 0, ids, g * 4, client_id="q",
                           seq=4, map_version=1, with_watermark=True)
    assert applied is True and wm == 4
    assert res.queue.depth() == 0
    after = np.array(pair["st0"].pull("users", 0, ids))
    assert np.allclose(after - before, g * (1 + 2 + 3 + 4))
    replay = dp.PushQueue.replay_journal(journal)
    assert [e["seq"] for e in replay["enqueued"]] == [1, 2, 3]
    assert [e["seq"] for e in replay["drained"]] == [1, 2, 3]
    assert np.allclose(replay["enqueued"][1]["rows"], g * 2)
    res.close()


def test_drain_stops_at_first_failure_preserving_order(served_pair,
                                                       blackhole):
    pair = served_pair
    res = dp.ResilientTransport(
        dp.GrpcTransport({0: pair["addr0"]}),
        policies={"push": dp.CallPolicy(budget_s=0.15, max_attempts=1)},
        hedge=False, queue_max=8, breaker_failures=1,
        breaker_cooldown_s=0.1,
    )
    ids = np.arange(2, dtype=np.int32)
    g = np.ones((2, 8), np.float32)
    res.update_addresses({0: blackhole})
    for seq in (1, 2):
        res.push(0, "users", 0, ids, g, client_id="d", seq=seq,
                 map_version=1)
    # still partitioned: the drain attempt fails and the backlog stays
    # whole and ordered
    time.sleep(0.15)
    assert res.drain_queued() == 0
    assert res.queue.depth(0) == 2
    res.update_addresses({0: pair["addr0"]})
    time.sleep(0.15)
    assert res.drain_queued() == 2
    assert res.queue.depth() == 0
    res.close()


# ------------------------------------------------------------------ #
# owner address book


def test_address_book_rides_registration_and_shard_map(tmp_path):
    from elasticdl_tpu.embedding.sharding import ShardMapOwner
    from elasticdl_tpu.master.journal import ControlPlaneJournal
    from elasticdl_tpu.master.membership import Membership
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

    journal = ControlPlaneJournal(str(tmp_path))
    membership = Membership(journal=journal)
    dispatcher = TaskDispatcher(
        training_shards=[("t", 0, 40)], records_per_task=10,
        shuffle=False,
    )
    emb = ShardMapOwner(num_shards=2, journal=journal)
    emb.register_table(SPEC)
    servicer = MasterServicer(dispatcher, membership, embedding=emb)
    resp = servicer.RegisterWorker(
        pb.RegisterWorkerRequest(
            worker_name="w0", data_plane_addr="127.0.0.1:1234"),
        None,
    )
    servicer.RegisterWorker(
        pb.RegisterWorkerRequest(worker_name="w1"), None)  # no endpoint
    map_resp = servicer.GetEmbeddingShardMap(
        pb.GetEmbeddingShardMapRequest(worker_id=resp.worker_id), None)
    assert list(map_resp.addr_worker_ids) == [resp.worker_id]
    assert list(map_resp.addrs) == ["127.0.0.1:1234"]
    view = tier.view_from_response(map_resp)
    assert view.addrs == ((resp.worker_id, "127.0.0.1:1234"),)
    journal.close()

    # a successor master replays the SAME address book
    successor = ControlPlaneJournal(str(tmp_path))
    restored = Membership(journal=successor)
    assert restored.data_addresses() == [
        (resp.worker_id, "127.0.0.1:1234")]
    successor.close()


def test_tier_refresh_adopts_address_book(served_pair):
    pair = served_pair
    tr = dp.GrpcTransport()
    view_with_addrs = sharding.ShardMapView(
        version=1, num_shards=2, owners=(0, 0), tables=(SPEC,),
        addrs=((0, pair["addr0"]),),
    )
    client = tier.EmbeddingTierClient(
        lambda: view_with_addrs, tr, client_id="bookworm")
    # the refresh inside __init__ adopted the book: pulls route
    rows = client.pull("users", np.array([1, 2], np.int64))
    assert rows.shape == (2, 8)
    assert tr.address_of(0) == pair["addr0"]
    client.close()
    tr.close()


# ------------------------------------------------------------------ #
# sim wire behind the shared contract (satellite)


def test_sim_wire_transport_implements_the_contract():
    st = EmbeddingShardStore(0, device=False)
    st.attach(make_view(replicas=((), ())))
    local = LocalTransport()
    local.register(st)
    sim = SimWireTransport(local, call_us=200, row_us=1)
    ids = np.arange(8, dtype=np.int32)
    t0 = time.perf_counter()
    rows, wm = sim.pull(0, "users", 0, ids, map_version=1,
                        with_watermark=True)
    assert time.perf_counter() - t0 >= 200e-6     # the modeled wire
    bare, _ = local.pull(0, "users", 0, ids, map_version=1,
                         with_watermark=True)
    assert np.allclose(rows, bare)
    assert sim.shard_watermark(0, "users", 0) == 0
    assert sim.owners() == [0]                    # registry passthrough


def test_resilient_transport_over_local_transport():
    """The robustness layer composes over ANY transport — deadline
    budgets degrade to retry bounds when the inner has no wire."""
    st = EmbeddingShardStore(0, device=False)
    st.attach(make_view(replicas=((), ())))
    local = LocalTransport()
    local.register(st)
    res = dp.ResilientTransport(local, queue_max=0)
    ids = np.arange(4, dtype=np.int32)
    rows, wm = res.pull(0, "users", 0, ids, map_version=1,
                        with_watermark=True)
    assert rows.shape == (4, 8) and wm == 0
    applied, wm = res.push(0, "users", 0, ids,
                           np.ones((4, 8), np.float32),
                           client_id="c", seq=1, map_version=1,
                           with_watermark=True)
    assert applied is True and wm == 1
    local.deregister(0)
    with pytest.raises(OwnerUnavailableError):
        res.pull(0, "users", 0, ids, map_version=1, with_watermark=True)
    res.close()
