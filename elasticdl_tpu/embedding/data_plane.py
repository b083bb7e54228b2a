"""The cross-host embedding data plane: real gRPC pull/push between
tier clients and owning stores, hardened for partitions (ISSUE 15).

Three layers, composable bottom-up:

1. **Wire** — `EmbeddingDataServicer` serves one worker's
   `EmbeddingShardStore` over five RPCs (`EmbeddingPull` /
   `EmbeddingPush` / `EmbeddingFetchShard` / `EmbeddingFetchDelta` /
   `EmbeddingWatermark`, hand-bound like proto/service.py — no
   grpcio-tools plugin on this image), from an `EmbeddingDataServer`
   each worker binds next to its observability endpoint. Id vectors
   travel as raw int32 bytes and rows as raw float32 bytes (one memcpy
   each way). The server honors the client's propagated gRPC deadline:
   a request arriving with (almost) no budget left is refused before
   any gather runs.

2. **Routing** — `GrpcTransport` implements transport.py's call
   contract over the master's OWNER ADDRESS BOOK (worker id -> data
   endpoint, riding the shard-map response): per-owner channels, an
   in-process short-circuit for the worker's own store, and the same
   request/response fault sites LocalTransport fires (`emb.pull` /
   `emb.pull.recv` / ...), so one chaos schedule drives either
   transport. gRPC failures map back to the tier's error vocabulary:
   FAILED_PRECONDITION -> StaleShardMapError, everything else ->
   OwnerUnavailableError (DeadlineExceededError for expired budgets).

3. **Robustness** — `ResilientTransport` wraps any inner transport
   with the RetryingMasterStub treatment, tuned for a data plane that
   must survive an owner partitioning away:

   - per-call DEADLINE BUDGETS: each logical call gets one budget
     (config `--embedding_rpc_deadline_ms`); retries and backoff
     sleeps spend it, and each attempt's wire deadline is the
     remaining budget split over the remaining attempts — a retry can
     never extend the caller's wait, and the budget propagates to the
     server as the gRPC deadline.
   - jittered exponential backoff RETRIES that re-send under the SAME
     client seq (the payload is untouched), so the store's
     exactly-once fence absorbs any ambiguous outcome.
   - per-OWNER CIRCUIT BREAKERS (proto/service.CircuitBreaker — the
     control plane's breaker, one per peer) with channel refresh on
     wedge: every `refresh_after` consecutive transport failures the
     owner's channel is rebuilt rather than trusted forever.
   - HEDGED READS: a pull whose primary has not answered after a
     p99-derived hedge delay races a replica; the first credible
     answer (replica credible iff its watermark is within the
     staleness bound of the highest watermark this transport has
     observed for the shard) wins, the loser is cancelled and counted.
   - the DEGRADED-MODE LADDER when an owner partitions away: hedge to
     a replica (`edl_emb_degraded_reads_total{mode="replica"}` when
     the primary actually failed, not merely lagged) -> the tier
     client serves staleness-bounded cache rows beyond `wm_probe`
     reach (mode="cache", counted in tier.py) -> block only when no
     bound can be honored (mode="blocked", counted here when every
     rung failed).
   - PUSHES QUEUE bounded-and-journaled behind an open breaker
     (`PushQueue`: an append-only journal so the partition window's
     writes are auditable and replayable) and DRAIN IN ORDER on
     reconnect — re-sent under their original seqs, so a heal can
     never double-apply (the bench's seq-fence audit) and a queued
     client keeps training through the partition instead of blocking.

`python -m elasticdl_tpu.embedding.data_plane --serve <spec.json>` runs
a standalone owner process (store + server + optional replica-sync
loop) — the multi-process half of `bench.py data_plane`.
"""

from __future__ import annotations

import base64
import json
import os
import threading
import time
from collections import deque
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import grpc
import numpy as np

from elasticdl_tpu.common import faults
from elasticdl_tpu.embedding import shm as _shm
from elasticdl_tpu.common.log_utils import default_logger
from elasticdl_tpu.embedding.store import StaleShardMapError
from elasticdl_tpu.embedding.transport import (
    DEGRADED_READS,
    OwnerUnavailableError,
)
from elasticdl_tpu.observability import reqtrace
from elasticdl_tpu.observability.registry import (
    default_registry,
    quantile_sorted,
)
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

logger = default_logger(__name__)

DATA_SERVICE_NAME = "elasticdl_tpu.EmbeddingData"

# rpc name -> (request type, response type); bound generically like the
# Master service (proto/service.py _RPCS)
_DATA_RPCS = {
    "EmbeddingPull": (pb.EmbeddingPullRequest, pb.EmbeddingPullResponse),
    "EmbeddingPush": (pb.EmbeddingPushRequest, pb.EmbeddingPushResponse),
    "EmbeddingFetchShard": (
        pb.EmbeddingFetchShardRequest, pb.EmbeddingFetchShardResponse),
    "EmbeddingFetchDelta": (
        pb.EmbeddingFetchDeltaRequest, pb.EmbeddingFetchDeltaResponse),
    "EmbeddingWatermark": (
        pb.EmbeddingWatermarkRequest, pb.EmbeddingWatermarkResponse),
    # wire-speed lanes (ISSUE 18)
    "EmbeddingPullMulti": (
        pb.EmbeddingPullMultiRequest, pb.EmbeddingPullMultiResponse),
    "EmbeddingWatermarkMulti": (
        pb.EmbeddingWatermarkMultiRequest,
        pb.EmbeddingWatermarkMultiResponse),
    "EmbeddingShmNegotiate": (
        pb.EmbeddingShmNegotiateRequest, pb.EmbeddingShmNegotiateResponse),
}

# server-streamed rpcs (ISSUE 18): one call, chunked frames — replica
# sync and shard migration stop paying a unary round-trip per chunk
_DATA_STREAM_RPCS = {
    "EmbeddingFetchShardStream": (
        pb.EmbeddingFetchShardRequest, pb.EmbeddingShardChunk),
    "EmbeddingFetchDeltaStream": (
        pb.EmbeddingFetchDeltaRequest, pb.EmbeddingDeltaChunk),
}

#: stream chunk sizing: target bytes of row payload per frame (rows per
#: frame = STREAM_CHUNK_BYTES / (dim * 4), floor 1); delta streams frame
#: by entry count instead. docs/performance.md discusses the tradeoff.
STREAM_CHUNK_BYTES = int(float(os.environ.get(
    "EDL_EMB_STREAM_CHUNK_KB", "512")) * 1024)
STREAM_DELTA_ENTRIES = 64

_reg = default_registry()
_RPC_CALLS = _reg.counter(
    "edl_emb_rpc_client_calls_total",
    "data-plane RPC attempts (per method, incl. retries)",
    labels=("method",))
_RPC_FAILURES = _reg.counter(
    "edl_emb_rpc_client_failures_total",
    "failed data-plane RPC attempts", labels=("method",))
_RPC_RETRIES = _reg.counter(
    "edl_emb_rpc_client_retries_total",
    "data-plane retries after a retryable failure (same client seq — "
    "the store's exactly-once fence absorbs re-sends)",
    labels=("method",))
_RPC_DEADLINE = _reg.counter(
    "edl_emb_rpc_client_deadline_exceeded_total",
    "data-plane attempts that ran out their deadline budget",
    labels=("method",))
_RPC_LATENCY = _reg.histogram(
    "edl_emb_rpc_client_latency_seconds",
    "successful data-plane call latency", labels=("method",))
_RPC_SERVER_CALLS = _reg.counter(
    "edl_emb_rpc_server_calls_total",
    "data-plane RPCs served by this owner", labels=("method",))
_RPC_SERVER_EXPIRED = _reg.counter(
    "edl_emb_rpc_server_deadline_expired_total",
    "requests refused because the propagated deadline had (almost) no "
    "budget left — serving them would burn owner CPU on an answer the "
    "client already abandoned")
_BREAKER_OPEN = _reg.gauge(
    "edl_emb_owner_breakers_open",
    "embedding owners whose data-plane circuit breaker is currently open")
_BREAKER_TRIPS = _reg.counter(
    "edl_emb_owner_breaker_trips_total",
    "per-owner data-plane breaker open transitions")
_CHANNEL_REFRESHES = _reg.counter(
    "edl_emb_rpc_channel_refreshes_total",
    "data-plane channels rebuilt after repeated transport failures")
_HEDGED = _reg.counter(
    "edl_emb_hedged_pulls_total",
    "pulls that launched a replica hedge after the hedge delay")
_HEDGE_WINS = _reg.counter(
    "edl_emb_hedge_wins_total",
    "hedged pulls the replica answered first (credibly)")
_HEDGE_CANCELLED = _reg.counter(
    "edl_emb_hedge_losers_cancelled_total",
    "hedge losers cancelled/abandoned after the winner answered")
_HEDGE_DELAY_MS = _reg.gauge(
    "edl_emb_hedge_delay_ms",
    "current hedge delay (p99-derived unless pinned by config)")
_QUEUE_DEPTH = _reg.gauge(
    "edl_emb_push_queue_depth",
    "pushes queued behind open owner breakers, fleet of owners combined")
_QUEUE_ENQUEUED = _reg.counter(
    "edl_emb_push_queue_enqueued_total",
    "pushes accepted into the bounded partition queue")
_QUEUE_DRAINED = _reg.counter(
    "edl_emb_push_queue_drained_total",
    "queued pushes re-sent (same seq) after the owner reconnected")
_QUEUE_REJECTED = _reg.counter(
    "edl_emb_push_queue_rejected_total",
    "pushes refused because the bounded queue was full (the caller "
    "blocks/raises instead — bounded memory is part of the contract)")
_COALESCED_TABLES = _reg.histogram(
    "edl_emb_rpc_coalesced_tables",
    "(table, shard) sub-pulls fused into each EmbeddingPullMulti call "
    "— the coalescing factor the per-call amortization rides on")
_STREAM_CHUNKS = _reg.counter(
    "edl_emb_stream_chunks_total",
    "frames served/consumed on the streaming fetch lanes, by method",
    labels=("method",))


# ------------------------------------------------------------------ #
# wire codec: numpy <-> raw little-endian bytes (one memcpy each way)


def ids_to_bytes(ids: np.ndarray) -> bytes:
    return np.ascontiguousarray(
        np.asarray(ids, np.int32)).astype("<i4", copy=False).tobytes()


def ids_from_bytes(data: bytes) -> np.ndarray:
    return np.frombuffer(data, dtype="<i4").astype(np.int32, copy=False)


def rows_to_bytes(rows: np.ndarray) -> bytes:
    return np.ascontiguousarray(
        np.asarray(rows, np.float32)).astype("<f4", copy=False).tobytes()


def rows_from_bytes(data: bytes, dim: int) -> np.ndarray:
    flat = np.frombuffer(data, dtype="<f4").astype(np.float32, copy=False)
    if dim <= 0:
        return flat.reshape(0, 0)
    return flat.reshape(-1, dim)


class DeadlineExceededError(OwnerUnavailableError):
    """A data-plane call ran out its deadline budget (the owner may or
    may not have applied it — the seq fence makes the re-send safe)."""


# ------------------------------------------------------------------ #
# fused serving helpers — pure store -> message functions shared by
# the gRPC servicer and the shared-memory ring dispatcher (the ring
# replaces the socket, not the codec)


def _serve_pull_multi(store, request) -> "pb.EmbeddingPullMultiResponse":
    """Serve one fused multi-(table, shard) pull: the flat id blob is
    segmented by `counts` with frombuffer views (no per-table copies
    in), the per-sub row blocks flatten into ONE response blob (one
    memcpy out), and the owner's full primary watermark set piggybacks.
    Raises StaleShardMapError for the caller to map onto its wire."""
    with reqtrace.stage("codec"):
        ids_flat = ids_from_bytes(request.ids)
    mv = request.map_version or None
    blocks: List[np.ndarray] = []
    dims: List[int] = []
    wms: List[int] = []
    off = 0
    with reqtrace.stage("store"):
        for table, shard, count in zip(request.tables, request.shards,
                                       request.counts):
            sub = ids_flat[off:off + count]
            off += count
            rows, wm = store.pull(
                table, int(shard), sub, map_version=mv,
                with_watermark=True, replica=request.replica)
            blocks.append(np.ascontiguousarray(
                np.asarray(rows, np.float32)).reshape(-1))
            dims.append(int(rows.shape[1]))
            wms.append(int(wm))
    with reqtrace.stage("codec"):
        rows_bytes = (
            np.concatenate(blocks).astype("<f4", copy=False).tobytes()
            if blocks else b"")
        resp = pb.EmbeddingPullMultiResponse(
            rows=rows_bytes, dims=dims, wms=wms)
    for t, s in store.resident_shards():
        resp.wm_tables.append(t)
        resp.wm_shards.append(int(s))
        resp.wm_values.append(int(store.shard_watermark(t, s)))
    return resp


def _serve_watermark_multi(store, request):
    return pb.EmbeddingWatermarkMultiResponse(wms=[
        int(store.shard_watermark(t, int(s), replica=request.replica))
        for t, s in zip(request.tables, request.shards)
    ])


def _decode_pull_multi(requests, resp):
    """Client side of the fused pull: segment the flat row blob into
    per-sub-request views (frombuffer — zero copies until the tier
    scatters into its output buffer) plus the piggybacked owner
    watermark map."""
    flat = np.frombuffer(resp.rows, dtype="<f4").astype(
        np.float32, copy=False)
    results = []
    off = 0
    for (_t, _s, ids), dim, wm in zip(requests, resp.dims, resp.wms):
        n = int(np.asarray(ids).shape[0])
        dim = int(dim)
        results.append((flat[off:off + n * dim].reshape(n, dim), int(wm)))
        off += n * dim
    owner_wms = {
        (t, int(s)): int(wm)
        for t, s, wm in zip(resp.wm_tables, resp.wm_shards, resp.wm_values)
    }
    return results, owner_wms


def _shm_dispatch(servicer, method_id: int, payload: bytes):
    """Serve one shared-memory ring request against the servicer's
    store. Mirrors the gRPC handlers' error mapping onto the ring's
    tiny status vocabulary (the 'shard map' marker keeps the client
    classifier routing to StaleShardMapError)."""
    store = servicer._store  # noqa: SLF001 - servicer-internal by design
    if store is None:
        return (_shm.S_STALE,
                b"stale shard map: no store bound on this owner yet")
    try:
        if method_id == _shm.M_PULL_MULTI:
            _RPC_SERVER_CALLS.inc(method="EmbeddingPullMulti")
            req = pb.EmbeddingPullMultiRequest.FromString(payload)
            resp = _serve_pull_multi(store, req)
        elif method_id == _shm.M_WATERMARK_MULTI:
            _RPC_SERVER_CALLS.inc(method="EmbeddingWatermarkMulti")
            req = pb.EmbeddingWatermarkMultiRequest.FromString(payload)
            resp = _serve_watermark_multi(store, req)
        elif method_id == _shm.M_PULL:
            _RPC_SERVER_CALLS.inc(method="EmbeddingPull")
            req = pb.EmbeddingPullRequest.FromString(payload)
            rows, wm = store.pull(
                req.table, req.shard, ids_from_bytes(req.ids),
                map_version=req.map_version or None,
                with_watermark=True, replica=req.replica)
            resp = pb.EmbeddingPullResponse(
                rows=rows_to_bytes(rows), dim=int(rows.shape[1]),
                wm=int(wm))
        elif method_id == _shm.M_PUSH:
            _RPC_SERVER_CALLS.inc(method="EmbeddingPush")
            req = pb.EmbeddingPushRequest.FromString(payload)
            applied, wm = store.push(
                req.table, req.shard, ids_from_bytes(req.ids),
                rows_from_bytes(req.rows, req.dim),
                client_id=req.client_id, seq=int(req.seq),
                map_version=req.map_version or None,
                scale=float(req.scale or 1.0), with_watermark=True)
            resp = pb.EmbeddingPushResponse(
                applied=bool(applied), wm=int(wm))
        elif method_id == _shm.M_WATERMARK:
            _RPC_SERVER_CALLS.inc(method="EmbeddingWatermark")
            req = pb.EmbeddingWatermarkRequest.FromString(payload)
            resp = pb.EmbeddingWatermarkResponse(wm=int(
                store.shard_watermark(req.table, req.shard,
                                      replica=req.replica)))
        else:
            return _shm.S_ERROR, f"unknown method {method_id}".encode()
    except StaleShardMapError as e:
        return _shm.S_STALE, f"stale shard map: {e}".encode("utf-8")
    except Exception as e:
        return _shm.S_ERROR, str(e).encode("utf-8")
    return _shm.S_OK, resp.SerializeToString()


# ------------------------------------------------------------------ #
# server side


class EmbeddingDataServicer:
    """Serves one worker's EmbeddingShardStore over the EmbeddingData
    RPCs. The store binds late (`bind_store`) so the endpoint can come
    up — and its address ride the RegisterWorker request — before the
    tier client exists to build the store."""

    #: refuse requests whose propagated deadline has less than this left:
    #: the client has already (or will immediately) abandon the answer
    MIN_BUDGET_S = 0.002

    def __init__(self, store=None):
        self._store = store

    def bind_store(self, store) -> None:
        self._store = store

    def _serve_guard(self, method: str, context) -> Any:
        _RPC_SERVER_CALLS.inc(method=method)
        if self._store is None:
            context.abort(
                grpc.StatusCode.FAILED_PRECONDITION,
                "stale shard map: no store bound on this owner yet",
            )
        remaining = None
        try:
            remaining = context.time_remaining()
        except Exception:
            # deadline propagation is advisory on exotic contexts (tests
            # with fakes); the RPC itself is served:
            # edl-lint: disable=EDL303
            remaining = None
        if remaining is not None and remaining < self.MIN_BUDGET_S:
            _RPC_SERVER_EXPIRED.inc()
            context.abort(
                grpc.StatusCode.DEADLINE_EXCEEDED,
                "propagated deadline budget exhausted before serve",
            )
        return self._store

    @staticmethod
    def _abort_stale(context, e: StaleShardMapError):
        # the marker "shard map" routes the client-side classifier back
        # to StaleShardMapError (GrpcTransport._map_error)
        context.abort(
            grpc.StatusCode.FAILED_PRECONDITION, f"stale shard map: {e}")

    def EmbeddingPull(self, request, context):
        store = self._serve_guard("EmbeddingPull", context)
        ids = ids_from_bytes(request.ids)
        try:
            rows, wm = store.pull(
                request.table, request.shard, ids,
                map_version=request.map_version or None,
                with_watermark=True, replica=request.replica,
            )
        except StaleShardMapError as e:
            self._abort_stale(context, e)
        return pb.EmbeddingPullResponse(
            rows=rows_to_bytes(rows), dim=int(rows.shape[1]), wm=int(wm))

    def EmbeddingPush(self, request, context):
        store = self._serve_guard("EmbeddingPush", context)
        ids = ids_from_bytes(request.ids)
        rows = rows_from_bytes(request.rows, request.dim)
        try:
            applied, wm = store.push(
                request.table, request.shard, ids, rows,
                client_id=request.client_id, seq=int(request.seq),
                map_version=request.map_version or None,
                scale=float(request.scale or 1.0), with_watermark=True,
            )
        except StaleShardMapError as e:
            self._abort_stale(context, e)
        return pb.EmbeddingPushResponse(applied=bool(applied), wm=int(wm))

    def EmbeddingFetchShard(self, request, context):
        store = self._serve_guard("EmbeddingFetchShard", context)
        try:
            payload = store.extract_shard(
                request.table, request.shard, replica=request.replica)
        except StaleShardMapError as e:
            self._abort_stale(context, e)
        rows = np.asarray(payload["rows"], np.float32)
        return pb.EmbeddingFetchShardResponse(
            rows=rows_to_bytes(rows),
            rows_n=int(rows.shape[0]), dim=int(rows.shape[1]),
            applied_json=json.dumps(payload["applied"]),
            wm=int(payload.get("wm", 0)),
        )

    def EmbeddingFetchDelta(self, request, context):
        store = self._serve_guard("EmbeddingFetchDelta", context)
        try:
            delta = store.fetch_delta(
                request.table, request.shard, int(request.since_wm))
        except StaleShardMapError as e:
            self._abort_stale(context, e)
        if delta is None:
            return pb.EmbeddingFetchDeltaResponse(found=False)
        resp = pb.EmbeddingFetchDeltaResponse(
            found=True, wm=int(delta["wm"]))
        for e in delta["entries"]:
            rows = np.asarray(e["rows"], np.float32)
            resp.entries.add(
                wm=int(e["wm"]), ids=ids_to_bytes(e["ids"]),
                rows=rows_to_bytes(rows),
                dim=int(rows.shape[1]) if rows.ndim == 2 else 0,
                scale=float(e.get("scale", 1.0)),
                client_id=str(e.get("client_id", "")),
                seq=int(e.get("seq", -1)),
            )
        return resp

    def EmbeddingWatermark(self, request, context):
        store = self._serve_guard("EmbeddingWatermark", context)
        try:
            wm = store.shard_watermark(
                request.table, request.shard, replica=request.replica)
        except StaleShardMapError as e:
            self._abort_stale(context, e)
        return pb.EmbeddingWatermarkResponse(wm=int(wm))

    # ---- wire-speed lanes (ISSUE 18) ------------------------------- #

    def bind_shm(self, shm_server) -> None:
        """Late-bind the shared-memory ring server (EmbeddingDataServer
        owns its lifetime) so EmbeddingShmNegotiate can mint rings."""
        self._shm_server = shm_server

    def EmbeddingPullMulti(self, request, context):
        store = self._serve_guard("EmbeddingPullMulti", context)
        # server-side diary: codec/store stages land via the TLS stack
        # inside _serve_pull_multi; retained tails surface in the
        # OWNER's flight bundles next to the client's
        rec = reqtrace.get_recorder()
        d = rec.start("serve", method="pull_multi")
        try:
            resp = _serve_pull_multi(store, request)
        except StaleShardMapError as e:
            rec.finish(d, "error", f"stale shard map: {e}")
            self._abort_stale(context, e)
        except BaseException as e:
            rec.finish(d, "error", repr(e))
            raise
        rec.finish(d, "ok")
        return resp

    def EmbeddingWatermarkMulti(self, request, context):
        store = self._serve_guard("EmbeddingWatermarkMulti", context)
        try:
            return _serve_watermark_multi(store, request)
        except StaleShardMapError as e:
            self._abort_stale(context, e)

    def EmbeddingShmNegotiate(self, request, context):
        # no store guard: negotiation only mints a ring; every ring
        # request re-checks store binding at serve time
        _RPC_SERVER_CALLS.inc(method="EmbeddingShmNegotiate")
        shm_server = getattr(self, "_shm_server", None)
        if shm_server is None:
            return pb.EmbeddingShmNegotiateResponse(ok=False)
        granted = shm_server.negotiate(int(request.slot_bytes))
        if granted is None:
            return pb.EmbeddingShmNegotiateResponse(ok=False)
        name, slot_bytes = granted
        logger.info("shm ring %s (%d B slots) negotiated for client "
                    "%s pid %d", name, slot_bytes,
                    request.client_host or "?", request.client_pid)
        return pb.EmbeddingShmNegotiateResponse(
            ok=True, segment=name, slot_bytes=int(slot_bytes))

    def EmbeddingFetchShardStream(self, request, context):
        store = self._serve_guard("EmbeddingFetchShardStream", context)
        try:
            payload = store.extract_shard(
                request.table, request.shard, replica=request.replica)
        except StaleShardMapError as e:
            self._abort_stale(context, e)
        rows = np.asarray(payload["rows"], np.float32)
        n, dim = int(rows.shape[0]), int(rows.shape[1])
        per_frame = max(1, STREAM_CHUNK_BYTES // max(1, dim * 4))
        off = 0
        first = True
        while True:
            end = min(n, off + per_frame)
            frame = pb.EmbeddingShardChunk(
                rows=rows_to_bytes(rows[off:end]), offset=off,
                last=end >= n)
            if first:
                # the fence rides the FIRST frame: a consumer that saw
                # frame 0 knows the full extent and the exactly-once
                # watermarks even if the stream dies right after
                frame.rows_n = n
                frame.dim = dim
                frame.applied_json = json.dumps(payload["applied"])
                frame.wm = int(payload.get("wm", 0))
                first = False
            _STREAM_CHUNKS.inc(method="EmbeddingFetchShardStream")
            yield frame
            off = end
            if off >= n:
                return

    def EmbeddingFetchDeltaStream(self, request, context):
        store = self._serve_guard("EmbeddingFetchDeltaStream", context)
        try:
            delta = store.fetch_delta(
                request.table, request.shard, int(request.since_wm))
        except StaleShardMapError as e:
            self._abort_stale(context, e)
        if delta is None:
            _STREAM_CHUNKS.inc(method="EmbeddingFetchDeltaStream")
            yield pb.EmbeddingDeltaChunk(found=False, last=True)
            return
        entries = delta["entries"]
        wm = int(delta["wm"])
        if not entries:
            _STREAM_CHUNKS.inc(method="EmbeddingFetchDeltaStream")
            yield pb.EmbeddingDeltaChunk(found=True, wm=wm, last=True)
            return
        for off in range(0, len(entries), STREAM_DELTA_ENTRIES):
            frame = pb.EmbeddingDeltaChunk(
                found=True, wm=wm,
                last=off + STREAM_DELTA_ENTRIES >= len(entries))
            for e in entries[off:off + STREAM_DELTA_ENTRIES]:
                erows = np.asarray(e["rows"], np.float32)
                frame.entries.add(
                    wm=int(e["wm"]), ids=ids_to_bytes(e["ids"]),
                    rows=rows_to_bytes(erows),
                    dim=int(erows.shape[1]) if erows.ndim == 2 else 0,
                    scale=float(e.get("scale", 1.0)),
                    client_id=str(e.get("client_id", "")),
                    seq=int(e.get("seq", -1)),
                )
            _STREAM_CHUNKS.inc(method="EmbeddingFetchDeltaStream")
            yield frame


def add_data_servicer(server: grpc.Server, servicer: Any) -> None:
    """Register the EmbeddingData handlers on a grpc server (generic
    handler API — same hand-binding as proto/service.add_master_servicer)."""
    handlers = {}
    for name, (req_t, _resp_t) in _DATA_RPCS.items():
        handlers[name] = grpc.unary_unary_rpc_method_handler(
            getattr(servicer, name),
            request_deserializer=req_t.FromString,
            response_serializer=lambda msg: msg.SerializeToString(),
        )
    for name, (req_t, _resp_t) in _DATA_STREAM_RPCS.items():
        handlers[name] = grpc.unary_stream_rpc_method_handler(
            getattr(servicer, name),
            request_deserializer=req_t.FromString,
            response_serializer=lambda msg: msg.SerializeToString(),
        )
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(DATA_SERVICE_NAME, handlers),)
    )


class EmbeddingDataServer:
    """One worker's data-plane endpoint: a grpc server over an
    EmbeddingDataServicer, bound next to the observability endpoint
    (worker/worker.py starts it before registration so its address can
    ride the RegisterWorker request)."""

    def __init__(self, store=None, host: str = "127.0.0.1",
                 max_workers: int = 8, shm: bool = True):
        from elasticdl_tpu.proto.service import make_server

        self.host = host
        self.servicer = EmbeddingDataServicer(store)
        self._server = make_server(max_workers=max_workers)
        add_data_servicer(self._server, self.servicer)
        self.port: Optional[int] = None
        self._shm_server = None
        if shm:
            from elasticdl_tpu.embedding.shm import HAVE_SHM, ShmRingServer

            if HAVE_SHM:
                self._shm_server = ShmRingServer(
                    lambda method, payload: _shm_dispatch(
                        self.servicer, method, payload))
                self.servicer.bind_shm(self._shm_server)

    def start(self, port: int = 0) -> int:
        bound = self._server.add_insecure_port(f"{self.host}:{port}")
        if not bound:
            raise RuntimeError(
                f"embedding data plane failed to bind {self.host}:{port}")
        self._server.start()
        self.port = bound
        logger.info("embedding data plane serving on %s:%d",
                    self.host, bound)
        return bound

    def stop(self, grace: float = 0.5) -> None:
        self._server.stop(grace)
        if self._shm_server is not None:
            self._shm_server.stop()

    @property
    def address(self) -> Optional[str]:
        return f"{self.host}:{self.port}" if self.port else None


# ------------------------------------------------------------------ #
# client side: routing


class DataPlaneStub:
    """Per-owner client stub over one channel (multicallables cached)."""

    def __init__(self, channel: grpc.Channel):
        self._methods = {}
        for name, (_req_t, resp_t) in _DATA_RPCS.items():
            self._methods[name] = channel.unary_unary(
                f"/{DATA_SERVICE_NAME}/{name}",
                request_serializer=lambda msg: msg.SerializeToString(),
                response_deserializer=resp_t.FromString,
            )
        for name, (_req_t, resp_t) in _DATA_STREAM_RPCS.items():
            self._methods[name] = channel.unary_stream(
                f"/{DATA_SERVICE_NAME}/{name}",
                request_serializer=lambda msg: msg.SerializeToString(),
                response_deserializer=resp_t.FromString,
            )

    def __getattr__(self, name: str):
        try:
            return self._methods[name]
        except KeyError as e:
            raise AttributeError(name) from e


class GrpcTransport:
    """transport.py's call contract over the owner address book.

    Owns one channel per peer owner; serves the LOCAL worker's own
    store in-process (a worker reading its own shard pays no wire).
    Every method takes an optional ``timeout_s`` — the deadline the
    ResilientTransport computed from its per-call budget — which rides
    to the server as the gRPC deadline (`accepts_deadline` advertises
    this; LocalTransport has no wire and no deadline)."""

    accepts_deadline = True

    def __init__(self, addresses: Optional[Dict[int, str]] = None,
                 default_timeout_s: float = 2.0, shm: bool = True):
        self._lock = threading.Lock()
        self._addrs: Dict[int, str] = dict(addresses or {})  # guarded_by: _lock
        self._channels: Dict[int, Tuple[grpc.Channel, DataPlaneStub]] = {}  # guarded_by: _lock
        self._local: Dict[int, Any] = {}                     # guarded_by: _lock
        self._default_timeout_s = default_timeout_s
        self._shm_enabled = bool(shm)
        self._shm_rings: Dict[int, Any] = {}                 # guarded_by: _lock
        self._shm_tried: Dict[int, str] = {}  # owner -> addr attempted; guarded_by: _lock
        self._shm_negotiating: Dict[int, threading.Thread] = {}  # guarded_by: _lock

    # ---- registry / address book ---------------------------------- #

    def register(self, store) -> None:
        with self._lock:
            self._local[store.owner] = store

    def deregister(self, owner: int) -> None:
        with self._lock:
            self._local.pop(owner, None)

    def owners(self) -> List[int]:
        with self._lock:
            return sorted(set(self._local) | set(self._addrs))

    def store_of(self, owner: int):
        with self._lock:
            store = self._local.get(owner)
        if store is None:
            raise OwnerUnavailableError(
                f"embedding owner {owner} is not local to this process "
                "(remote shards move via fetch_shard, not store_of)"
            )
        return store

    def update_addresses(self, addresses: Dict[int, str]) -> None:
        """Adopt the freshest owner address book (the shard-map
        response's). A changed address drops the cached channel — the
        old owner process is gone; its channel must not be trusted."""
        drop = []
        rings = []
        with self._lock:
            for owner, addr in addresses.items():
                owner = int(owner)
                if self._addrs.get(owner) != addr:
                    self._addrs[owner] = addr
                    drop.append(owner)
            for owner in drop:
                self._channels.pop(owner, None)
                # the shm short-circuit never outlives the address that
                # negotiated it: a moved/blackholed owner must not keep
                # serving through a stale ring
                ring = self._shm_rings.pop(owner, None)
                if ring is not None:
                    rings.append(ring)
        for ring in rings:
            ring.close()

    def address_of(self, owner: int) -> Optional[str]:
        with self._lock:
            return self._addrs.get(owner)

    def refresh_channel(self, owner: int) -> None:
        """Drop the cached channel so the next call rebuilds it (the
        ResilientTransport's wedge recovery — a subchannel that got stuck
        across an owner restart must not be trusted forever). The old
        channel is NOT force-closed: close() cancels in-flight RPCs and
        the transport is shared across threads."""
        with self._lock:
            self._channels.pop(owner, None)
        _CHANNEL_REFRESHES.inc()

    def _stub(self, owner: int) -> DataPlaneStub:
        with self._lock:
            entry = self._channels.get(owner)
            if entry is not None:
                return entry[1]
            addr = self._addrs.get(owner)
        if addr is None:
            raise OwnerUnavailableError(
                f"embedding owner {owner} has no data-plane address "
                "(dead worker, or not yet in the address book)"
            )
        from elasticdl_tpu.proto.service import make_channel

        channel = make_channel(addr)
        stub = DataPlaneStub(channel)
        with self._lock:
            # a concurrent builder may have won; keep the first
            entry = self._channels.setdefault(owner, (channel, stub))
        return entry[1]

    # ---- error mapping -------------------------------------------- #

    @staticmethod
    def _map_error(e: BaseException, owner: int,
                   method: str) -> BaseException:
        """gRPC failure -> the tier's error vocabulary. The wrapped
        original rides as __cause__ for forensics."""
        code = details = None
        try:
            c = getattr(e, "code", None)
            code = c() if callable(c) else None
            d = getattr(e, "details", None)
            details = str(d()) if callable(d) else ""
        except Exception:
            # classification-only; an exotic error object is simply an
            # unavailable owner: edl-lint: disable=EDL303
            pass
        if (code == grpc.StatusCode.FAILED_PRECONDITION
                and "shard" in (details or "")):
            return StaleShardMapError(details)
        if code == grpc.StatusCode.DEADLINE_EXCEEDED:
            return DeadlineExceededError(
                f"{method} to owner {owner} exceeded its deadline")
        return OwnerUnavailableError(
            f"{method} to owner {owner} failed"
            f" ({code or type(e).__name__}): {details or e}")

    def _call(self, method: str, owner: int, request,
              timeout_s: Optional[float]):
        stub = self._stub(owner)
        try:
            with reqtrace.stage("wire"):
                return getattr(stub, method)(
                    request,
                    timeout=(timeout_s if timeout_s is not None
                             else self._default_timeout_s),
                )
        except grpc.RpcError as e:
            raise self._map_error(e, owner, method) from e

    # ---- same-host shared-memory short-circuit (ISSUE 18) ---------- #

    def _shm_ring(self, owner: int, timeout_s: Optional[float]):
        """The owner's attached ring, kicking off negotiation on first
        use. Negotiation is attempted AT MOST ONCE per (owner,
        address) — a declined/failed negotiate must not tax every
        later call, and a partitioned owner must not pay a negotiate
        round per pull on top of its gRPC deadline — and it runs in a
        BACKGROUND thread: the negotiate RPC + segment attach cost
        ~10ms on a loaded box, and the call that happened to arrive
        first must not eat that on its latency; it rides the socket
        while the ring comes up."""
        if not self._shm_enabled:
            return None
        if not _shm.HAVE_SHM:
            return None
        with self._lock:
            ring = self._shm_rings.get(owner)
            if ring is not None:
                return ring
            addr = self._addrs.get(owner)
            if addr is None or self._shm_tried.get(owner) == addr:
                return None
            self._shm_tried[owner] = addr
        host = addr.rsplit(":", 1)[0]
        if not _shm.same_host(host):
            return None
        t = threading.Thread(target=self._negotiate_ring,
                             args=(owner, addr),
                             name=f"edl-shm-negotiate-{owner}",
                             daemon=True)
        with self._lock:
            self._shm_negotiating[owner] = t
        t.start()
        return None

    def _negotiate_ring(self, owner: int, addr: str) -> None:
        """Background half of `_shm_ring`: one negotiate RPC, one
        attach, publish the ring (or give up — the gRPC lane keeps
        serving either way). `addr` is the address book entry the
        negotiation was initiated for: if the owner moved while the
        RPC was in flight, the ring must NOT be published —
        `update_addresses` already dropped this owner's lane, and a
        late publish would resurrect a short-circuit to the old
        process."""
        import socket

        try:
            try:
                resp = self._call(
                    "EmbeddingShmNegotiate", owner,
                    pb.EmbeddingShmNegotiateRequest(
                        client_host=socket.gethostname(),
                        client_pid=os.getpid(),
                        slot_bytes=_shm.DEFAULT_SLOT_BYTES),
                    min(self._default_timeout_s, 1.0))
            except OwnerUnavailableError:
                _shm.SHM_FALLBACKS.inc(reason="negotiate")
                return
            if not resp.ok:
                return
            try:
                ring = _shm.ShmRingClient(resp.segment,
                                          int(resp.slot_bytes))
            except _shm.ShmRingError as e:
                logger.warning("shm attach to owner %d failed: %s",
                               owner, e)
                _shm.SHM_FALLBACKS.inc(reason="attach")
                return
            with self._lock:
                if self._addrs.get(owner) != addr:
                    stale, ring = ring, None
                else:
                    # a concurrent negotiator may have won; keep the
                    # first
                    ring = self._shm_rings.setdefault(owner, ring)
            if ring is None:
                stale.close()
                _shm.SHM_FALLBACKS.inc(reason="stale")
                logger.warning(
                    "shm negotiate to owner %d raced an address change; "
                    "ring discarded", owner)
                return
            logger.info("shm short-circuit to owner %d via %s", owner,
                        resp.segment)
        finally:
            with self._lock:
                self._shm_negotiating.pop(owner, None)

    def _drop_ring(self, owner: int, reason: str) -> None:
        with self._lock:
            ring = self._shm_rings.pop(owner, None)
        if ring is not None:
            ring.close()
            _shm.SHM_FALLBACKS.inc(reason=reason)
            logger.warning(
                "shm ring to owner %d dropped (%s); gRPC lane takes over",
                owner, reason)

    def _shm_call(self, owner: int, method_id: int, req_bytes: bytes,
                  timeout_s: Optional[float]):
        """One ring round-trip, or None when the shm lane is
        unavailable (caller proceeds over gRPC). Ring failures drop
        the ring — the segment is gone or the owner stopped serving
        it; gRPC is the lane that still has liveness semantics."""
        ring = self._shm_ring(owner, timeout_s)
        if ring is None:
            return None
        if len(req_bytes) > ring.slot_bytes:
            # this one request outgrew the slot; the ring itself is
            # fine — fall back per-call without dropping it
            _shm.SHM_FALLBACKS.inc(reason="too_big")
            return None
        try:
            return ring.call(
                method_id, req_bytes,
                timeout_s=min(timeout_s or self._default_timeout_s, 1.0))
        except _shm.ShmRingTimeout:
            self._drop_ring(owner, "timeout")
            return None
        except _shm.ShmRingError:
            self._drop_ring(owner, "gone")
            return None

    def _shm_status(self, owner: int, method: str, status: int,
                    payload: bytes):
        detail = payload.decode("utf-8", "replace")
        if status == _shm.S_STALE:
            raise StaleShardMapError(detail)
        raise OwnerUnavailableError(
            f"{method} to owner {owner} failed over shm: {detail}")

    # ---- the transport contract ----------------------------------- #

    def pull(self, owner: int, table: str, shard: int,
             local_ids: np.ndarray, map_version: Optional[int] = None,
             with_watermark: bool = False, replica: bool = False,
             timeout_s: Optional[float] = None):
        faults.fire("emb.pull")
        with self._lock:
            local = self._local.get(owner)
        if local is not None:
            with reqtrace.stage("store"):
                out = local.pull(
                    table, shard, local_ids, map_version=map_version,
                    with_watermark=True, replica=replica)
            faults.fire("emb.pull.recv")
            rows, wm = out
            return (rows, wm) if with_watermark else rows
        with reqtrace.stage("codec"):
            req = pb.EmbeddingPullRequest(
                table=table, shard=int(shard),
                ids=ids_to_bytes(local_ids),
                map_version=int(map_version or 0),
                with_watermark=True, replica=bool(replica),
            )
        resp = self._call("EmbeddingPull", owner, req, timeout_s)
        faults.fire("emb.pull.recv")
        with reqtrace.stage("codec"):
            rows = rows_from_bytes(resp.rows, resp.dim)
        return (rows, int(resp.wm)) if with_watermark else rows

    def push(self, owner: int, table: str, shard: int,
             local_ids: np.ndarray, rows: np.ndarray, *, client_id: str,
             seq: int, map_version: Optional[int] = None,
             scale: float = 1.0, with_watermark: bool = False,
             timeout_s: Optional[float] = None):
        faults.fire("emb.push")
        with self._lock:
            local = self._local.get(owner)
        if local is not None:
            with reqtrace.stage("store"):
                applied, wm = local.push(
                    table, shard, local_ids, rows, client_id=client_id,
                    seq=seq, map_version=map_version, scale=scale,
                    with_watermark=True)
            faults.fire("emb.push.recv")
            return (applied, wm) if with_watermark else applied
        dim = int(rows.shape[1]) if rows.ndim == 2 else 0
        with reqtrace.stage("codec"):
            req = pb.EmbeddingPushRequest(
                table=table, shard=int(shard),
                ids=ids_to_bytes(local_ids), rows=rows_to_bytes(rows),
                dim=dim, client_id=client_id, seq=int(seq),
                map_version=int(map_version or 0), scale=float(scale),
                with_watermark=True,
            )
        resp = self._call("EmbeddingPush", owner, req, timeout_s)
        # lost-ack injection: the owner DID apply; the caller never
        # hears back and re-sends under the same seq (fence absorbs)
        faults.fire("emb.push.recv")
        applied, wm = bool(resp.applied), int(resp.wm)
        return (applied, wm) if with_watermark else applied

    def fetch_shard(self, owner: int, table: str, shard: int,
                    timeout_s: Optional[float] = None) -> Dict[str, Any]:
        """Shard migration copy — served over the streaming lane (one
        call, chunked frames, fence in frame 0) and assembled back
        into the unary payload shape every caller already expects."""
        faults.fire("emb.fetch_shard")
        with self._lock:
            local = self._local.get(owner)
        if local is not None:
            payload = local.extract_shard(table, shard)
            faults.fire("emb.fetch_shard.recv")
            return payload
        stub = self._stub(owner)
        req = pb.EmbeddingFetchShardRequest(table=table, shard=int(shard))
        buf = None
        dim = 0
        meta: Dict[str, Any] = {"applied": {}, "wm": 0}
        try:
            for frame in stub.EmbeddingFetchShardStream(
                    req, timeout=(timeout_s if timeout_s is not None
                                  else self._default_timeout_s)):
                _STREAM_CHUNKS.inc(method="EmbeddingFetchShardStream.recv")
                if buf is None:
                    dim = int(frame.dim)
                    buf = np.zeros((int(frame.rows_n), dim), np.float32)
                    meta = {
                        "applied": {
                            str(k): int(v) for k, v in json.loads(
                                frame.applied_json or "{}").items()},
                        "wm": int(frame.wm),
                    }
                if frame.rows:
                    blk = rows_from_bytes(frame.rows, dim)
                    buf[frame.offset:frame.offset + blk.shape[0]] = blk
        except grpc.RpcError as e:
            raise self._map_error(e, owner, "EmbeddingFetchShardStream") \
                from e
        if buf is None:
            raise OwnerUnavailableError(
                f"fetch_shard {table}/{shard}: owner {owner} closed the "
                "stream before the first frame")
        faults.fire("emb.fetch_shard.recv")
        return {"rows": buf, "applied": meta["applied"],
                "wm": meta["wm"]}

    def shard_watermark(self, owner: int, table: str, shard: int,
                        replica: bool = False,
                        timeout_s: Optional[float] = None) -> int:
        faults.fire("emb.watermark")
        with self._lock:
            local = self._local.get(owner)
        if local is not None:
            return local.shard_watermark(table, shard, replica=replica)
        resp = self._call(
            "EmbeddingWatermark", owner,
            pb.EmbeddingWatermarkRequest(
                table=table, shard=int(shard), replica=bool(replica)),
            timeout_s,
        )
        return int(resp.wm)

    def fetch_delta(self, owner: int, table: str, shard: int,
                    since_wm: int,
                    timeout_s: Optional[float] = None,
                    ) -> Optional[Dict[str, Any]]:
        faults.fire("emb.fetch_delta")
        with self._lock:
            local = self._local.get(owner)
        if local is not None:
            delta = local.fetch_delta(table, shard, since_wm)
            faults.fire("emb.fetch_delta.recv")
            return delta
        resp = self._call(
            "EmbeddingFetchDelta", owner,
            pb.EmbeddingFetchDeltaRequest(
                table=table, shard=int(shard), since_wm=int(since_wm)),
            timeout_s,
        )
        faults.fire("emb.fetch_delta.recv")
        if not resp.found:
            return None
        return {
            "wm": int(resp.wm),
            "entries": [
                {
                    "wm": int(e.wm),
                    "ids": ids_from_bytes(e.ids),
                    "rows": rows_from_bytes(e.rows, e.dim),
                    "scale": float(e.scale),
                    "client_id": e.client_id,
                    "seq": int(e.seq),
                }
                for e in resp.entries
            ],
        }

    # ---- wire-speed lanes (ISSUE 18) ------------------------------- #

    def pull_multi(self, owner: int, requests,
                   map_version: Optional[int] = None,
                   replica: bool = False,
                   timeout_s: Optional[float] = None):
        """Fused multi-(table, shard) pull — LocalTransport.pull_multi's
        contract over one RPC (or one shm ring round-trip when the
        owner is same-host). One request-side and one response-side
        fault site per FUSED call: dropping it loses every sub-pull
        together, exactly what one lost wire call does."""
        faults.fire("emb.pull")
        _COALESCED_TABLES.observe(float(len(requests)))
        with self._lock:
            local = self._local.get(owner)
        if local is not None:
            with reqtrace.stage("store"):
                results = [
                    local.pull(t, s, ids, map_version=map_version,
                               with_watermark=True, replica=replica)
                    for t, s, ids in requests
                ]
                owner_wms = {
                    key: local.shard_watermark(*key)
                    for key in local.resident_shards()
                }
            faults.fire("emb.pull.recv")
            return results, owner_wms
        with reqtrace.stage("codec"):
            req = pb.EmbeddingPullMultiRequest(
                tables=[t for t, _, _ in requests],
                shards=[int(s) for _, s, _ in requests],
                counts=[int(np.asarray(ids).shape[0])
                        for _, _, ids in requests],
                ids=ids_to_bytes(
                    np.concatenate([
                        np.asarray(ids, np.int32).reshape(-1)
                        for _, _, ids in requests
                    ]) if requests else np.zeros((0,), np.int32)),
                map_version=int(map_version or 0),
                replica=bool(replica),
            )
            req_bytes = req.SerializeToString()
        got = self._shm_call(owner, _shm.M_PULL_MULTI,
                             req_bytes, timeout_s)
        if got is not None:
            status, payload = got
            if status != _shm.S_OK:
                self._shm_status(owner, "pull_multi", status, payload)
            with reqtrace.stage("codec"):
                resp = pb.EmbeddingPullMultiResponse.FromString(payload)
        else:
            resp = self._call("EmbeddingPullMulti", owner, req, timeout_s)
        faults.fire("emb.pull.recv")
        with reqtrace.stage("codec"):
            return _decode_pull_multi(requests, resp)

    def watermark_multi(self, owner: int, pairs, replica: bool = False,
                        timeout_s: Optional[float] = None):
        faults.fire("emb.watermark")
        with self._lock:
            local = self._local.get(owner)
        if local is not None:
            return [local.shard_watermark(t, s, replica=replica)
                    for t, s in pairs]
        req = pb.EmbeddingWatermarkMultiRequest(
            tables=[t for t, _ in pairs],
            shards=[int(s) for _, s in pairs],
            replica=bool(replica),
        )
        got = self._shm_call(owner, _shm.M_WATERMARK_MULTI,
                             req.SerializeToString(), timeout_s)
        if got is not None:
            status, payload = got
            if status != _shm.S_OK:
                self._shm_status(owner, "watermark_multi", status, payload)
            resp = pb.EmbeddingWatermarkMultiResponse.FromString(payload)
        else:
            resp = self._call(
                "EmbeddingWatermarkMulti", owner, req, timeout_s)
        return [int(wm) for wm in resp.wms]

    def fetch_delta_stream(self, owner: int, table: str, shard: int,
                           since_wm: int, chunk_entries: int = 64,
                           timeout_s: Optional[float] = None):
        """Streaming replica sync (transport.py's reference framing
        over a real server stream). A mid-stream transport failure
        surfaces as OwnerUnavailableError from the generator — the
        caller resumes from whatever watermark its applied prefix
        reached."""
        faults.fire("emb.fetch_delta")
        with self._lock:
            local = self._local.get(owner)
        if local is not None:
            from elasticdl_tpu.embedding.transport import _delta_frames

            delta = local.fetch_delta(table, shard, since_wm)
            faults.fire("emb.fetch_delta.recv")
            return _delta_frames(delta, chunk_entries)
        stub = self._stub(owner)
        req = pb.EmbeddingFetchDeltaRequest(
            table=table, shard=int(shard), since_wm=int(since_wm))

        def gen():
            try:
                for frame in stub.EmbeddingFetchDeltaStream(
                        req, timeout=(timeout_s if timeout_s is not None
                                      else self._default_timeout_s)):
                    _STREAM_CHUNKS.inc(
                        method="EmbeddingFetchDeltaStream.recv")
                    yield {
                        "found": bool(frame.found),
                        "wm": int(frame.wm),
                        "entries": [
                            {
                                "wm": int(e.wm),
                                "ids": ids_from_bytes(e.ids),
                                "rows": rows_from_bytes(e.rows, e.dim),
                                "scale": float(e.scale),
                                "client_id": e.client_id,
                                "seq": int(e.seq),
                            }
                            for e in frame.entries
                        ],
                        "last": bool(frame.last),
                    }
                    if not frame.found:
                        return
            except grpc.RpcError as e:
                raise self._map_error(
                    e, owner, "EmbeddingFetchDeltaStream") from e
            faults.fire("emb.fetch_delta.recv")

        return gen()

    def close(self) -> None:
        with self._lock:
            channels = [c for c, _ in self._channels.values()]
            self._channels.clear()
            rings = list(self._shm_rings.values())
            self._shm_rings.clear()
        for ring in rings:
            ring.close()
        for c in channels:
            try:
                c.close()
            except Exception:
                logger.debug("channel close failed", exc_info=True)


# ------------------------------------------------------------------ #
# robustness layer


@dataclass(frozen=True)
class CallPolicy:
    """Per-method deadline budget and retry shape. `budget_s` bounds
    the WHOLE logical call — attempts, backoff sleeps, and hedges all
    spend it; each attempt's wire deadline is the remaining budget
    split over the remaining attempts."""

    budget_s: float
    max_attempts: int = 3


def default_policies(budget_s: float = 2.0) -> Dict[str, CallPolicy]:
    return {
        "pull": CallPolicy(budget_s=budget_s, max_attempts=3),
        # one fused call IS one wire call: same budget shape as pull
        "pull_multi": CallPolicy(budget_s=budget_s, max_attempts=3),
        "push": CallPolicy(budget_s=budget_s, max_attempts=3),
        # a shard copy is bulk data (recovery path, not the hot path)
        "fetch_shard": CallPolicy(budget_s=max(30.0, budget_s),
                                  max_attempts=2),
        "fetch_delta": CallPolicy(budget_s=max(5.0, budget_s),
                                  max_attempts=2),
        "watermark": CallPolicy(budget_s=min(1.0, budget_s),
                                max_attempts=2),
    }


class PushQueue:
    """Bounded, journaled FIFO of pushes parked behind an open owner
    breaker. The journal is an append-only jsonl (torn-tail tolerant,
    arrays base64'd) recording every `enqueue` and every `drain`, so
    the partition window's writes are auditable after the fact and the
    bench's replay check can reconstruct exactly what was parked and
    in what order it drained. Entries drain IN ENQUEUE ORDER per owner
    — a later seq must never reach the store before an earlier one, or
    the earlier one's drain would be swallowed as a duplicate."""

    def __init__(self, journal_path: str = "", max_entries: int = 1024):
        self._lock = threading.Lock()
        self._by_owner: Dict[int, deque] = {}       # guarded_by: _lock
        self._depth = 0                             # guarded_by: _lock
        self.max_entries = int(max_entries)
        self._journal_path = journal_path
        self._journal_failed = False
        if journal_path:
            os.makedirs(os.path.dirname(journal_path) or ".",
                        exist_ok=True)

    def _journal(self, record: Dict[str, Any]) -> None:
        if not self._journal_path or self._journal_failed:
            return
        try:
            # journaling inside the queue's critical section is the
            # replay-identity invariant (journal order == deque order);
            # plain buffered append, no fsync — see enqueue():
            # edl-lint: disable=EDL103
            with open(self._journal_path, "a") as f:
                f.write(json.dumps(record) + "\n")
        except OSError:
            # one-shot loud disable — queueing must not die with the disk
            self._journal_failed = True
            logger.exception(
                "push-queue journal %s failed; journaling disabled",
                self._journal_path)

    def depth(self, owner: Optional[int] = None) -> int:
        with self._lock:
            if owner is None:
                return self._depth
            return len(self._by_owner.get(owner, ()))

    def enqueue(self, entry: Dict[str, Any]) -> bool:
        """Park one push (False = full; the caller must block/raise —
        unbounded queueing would turn a partition into an OOM)."""
        with self._lock:
            if self._depth >= self.max_entries:
                _QUEUE_REJECTED.inc()
                return False
            self._by_owner.setdefault(int(entry["owner"]), deque()).append(
                entry)
            self._depth += 1
            _QUEUE_DEPTH.set(self._depth)
            # journaled INSIDE the critical section: two concurrent
            # enqueues must journal in deque order or the replay-
            # identity audit (enqueue stream == drain stream) breaks
            # spuriously. Plain buffered append, no fsync under lock.
            self._journal({
                "op": "enqueue", "owner": int(entry["owner"]),
                "table": entry["table"], "shard": int(entry["shard"]),
                "client_id": entry["client_id"], "seq": int(entry["seq"]),
                "scale": float(entry["scale"]),
                "map_version": entry["map_version"],
                "ids": base64.b64encode(
                    ids_to_bytes(entry["ids"])).decode(),
                "rows": base64.b64encode(
                    rows_to_bytes(entry["rows"])).decode(),
                "dim": int(entry["rows"].shape[1]),
            })
        _QUEUE_ENQUEUED.inc()
        return True

    def peek(self, owner: int) -> Optional[Dict[str, Any]]:
        with self._lock:
            q = self._by_owner.get(owner)
            return q[0] if q else None

    def pop_drained(self, owner: int) -> None:
        with self._lock:
            q = self._by_owner.get(owner)
            if not q:
                return
            entry = q.popleft()
            if not q:
                self._by_owner.pop(owner, None)
            self._depth -= 1
            _QUEUE_DEPTH.set(self._depth)
            # under the lock for the same reason as enqueue's record
            self._journal({
                "op": "drain", "owner": int(entry["owner"]),
                "table": entry["table"], "shard": int(entry["shard"]),
                "client_id": entry["client_id"], "seq": int(entry["seq"]),
            })
        _QUEUE_DRAINED.inc()

    def owners_with_backlog(self) -> List[int]:
        with self._lock:
            return sorted(self._by_owner)

    @staticmethod
    def replay_journal(path: str) -> Dict[str, List[Dict[str, Any]]]:
        """Parse the journal back into its enqueue/drain streams (torn
        tail dropped) — the bench's replay-identity audit re-applies
        the enqueue stream and checks the drain stream retired exactly
        the enqueued (client_id, seq) pairs in order."""
        enqueued: List[Dict[str, Any]] = []
        drained: List[Dict[str, Any]] = []
        try:
            with open(path) as f:
                lines = f.read().splitlines()
        except OSError:
            return {"enqueued": [], "drained": []}
        for line in lines:
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # torn tail
            if rec.get("op") == "enqueue":
                rec = dict(rec)
                rec["ids"] = ids_from_bytes(
                    base64.b64decode(rec["ids"]))
                rec["rows"] = rows_from_bytes(
                    base64.b64decode(rec["rows"]), int(rec["dim"]))
                enqueued.append(rec)
            elif rec.get("op") == "drain":
                drained.append(rec)
        return {"enqueued": enqueued, "drained": drained}


#: hedge-delay floor: below this the hedge races scheduler noise, and
#: every pull would pay a pointless executor round-trip
HEDGE_FLOOR_MS = 1.0
#: p99 window backing the derived hedge delay
_HEDGE_WINDOW = 128


def _diary_status(d: "reqtrace.Diary") -> str:
    """A call that answered but leaned on the degraded ladder (replica
    serve, hedge win) finishes its diary as `degraded` — the tail
    sampler retains those unconditionally."""
    for ev in d.events:
        if ev.get("name") == "degraded":
            return "degraded"
    return "ok"


class ResilientTransport:
    """The robustness layer over any transport (docstring at module
    top). Implements the same call contract, so the tier client, the
    replica sync loop, and reshard.py all harden for free."""

    RETRYABLE = (OwnerUnavailableError, faults.FaultInjected)

    def __init__(
        self,
        inner,
        policies: Optional[Dict[str, CallPolicy]] = None,
        staleness_bound: int = 1,
        hedge_delay_ms: float = 0.0,
        hedge: bool = True,
        view_fn: Optional[Callable[[], Any]] = None,
        queue_journal: str = "",
        queue_max: int = 1024,
        breaker_failures: int = 3,
        breaker_cooldown_s: float = 1.0,
        refresh_after: int = 3,
        backoff_base_s: float = 0.02,
        backoff_max_s: float = 0.5,
        rng=None,
        sleep: Callable[[float], None] = time.sleep,
        trace_tag: str = "",
    ):
        import random

        from elasticdl_tpu.proto.service import CircuitBreaker

        self._inner = inner
        # stamped into every request diary's meta: lets one process
        # running several transports (a hedged lane and an unhedged
        # control, a reader and a writer) slice its retained tail per
        # lane instead of per process
        self._trace_tag = str(trace_tag)
        self._policies = default_policies()
        if policies:
            self._policies.update(policies)
        self.staleness_bound = max(0, int(staleness_bound))
        self._hedge_enabled = bool(hedge)
        self._hedge_delay_ms = float(hedge_delay_ms)   # 0 = p99-derived
        self._view_fn = view_fn
        self._breaker_cls = CircuitBreaker
        self._breaker_failures = breaker_failures
        self._breaker_cooldown_s = breaker_cooldown_s
        self._refresh_after = max(1, refresh_after)
        self._backoff_base_s = backoff_base_s
        self._backoff_max_s = backoff_max_s
        self._rng = rng if rng is not None else random.Random()
        self._sleep = sleep
        self._lock = threading.Lock()
        self._breakers: Dict[int, Any] = {}            # guarded_by: _lock
        self._consec_failures: Dict[int, int] = {}     # guarded_by: _lock
        self._observed_wm: Dict[Tuple[str, int], int] = {}  # guarded_by: _lock
        self._pull_lat: "deque[float]" = deque(maxlen=_HEDGE_WINDOW)  # guarded_by: _lock
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pool_lock = threading.Lock()
        self._inner_takes_deadline = bool(
            getattr(inner, "accepts_deadline", False))
        self.queue = (PushQueue(queue_journal, queue_max)
                      if queue_max > 0 else None)
        self._drain_lock = threading.Lock()

    # ---- plumbing -------------------------------------------------- #

    def __getattr__(self, name):
        # registry surface (register/deregister/store_of/owners/
        # update_addresses/...) passes straight through to the inner
        # transport
        return getattr(self._inner, name)

    def set_view_fn(self, view_fn: Callable[[], Any]) -> None:
        """Late-bind the shard-map view source (the tier client exists
        after the transport) — what hedging uses to find replicas and
        what drains use to re-route a moved shard."""
        self._view_fn = view_fn

    def _breaker(self, owner: int):
        with self._lock:
            br = self._breakers.get(owner)
            if br is None:
                br = self._breaker_cls(
                    failure_threshold=self._breaker_failures,
                    cooldown_s=self._breaker_cooldown_s,
                    # per-owner data-plane breakers keep their own
                    # edl_emb_owner_* metrics; the inherited master
                    # gauges/logs would misread a partitioned owner as
                    # a master outage (and mask a real one on close)
                    telemetry=False,
                )
                self._breakers[owner] = br
            return br

    def owner_degraded(self, owner: int) -> bool:
        """True while the owner's circuit is open — the tier client's
        signal that cache hits are being served beyond `wm_probe` reach
        (degraded mode \"cache\")."""
        with self._lock:
            br = self._breakers.get(owner)
        return br is not None and br.is_open

    def degraded_owners(self) -> List[int]:
        with self._lock:
            items = list(self._breakers.items())
        return [o for o, br in items if br.is_open]

    def observed_wm(self, table: str, shard: int) -> int:
        with self._lock:
            return self._observed_wm.get((table, shard), 0)

    def _note_wm(self, table: str, shard: int, wm: int) -> None:
        with self._lock:
            key = (table, shard)
            if wm > self._observed_wm.get(key, 0):
                self._observed_wm[key] = wm

    def _note_success(self, owner: int) -> None:
        br = self._breaker(owner)
        was_open = br.is_open
        br.record_success()
        with self._lock:
            self._consec_failures[owner] = 0
            open_now = sum(1 for b in self._breakers.values() if b.is_open)
        _BREAKER_OPEN.set(open_now)
        if was_open:
            logger.warning(
                "embedding owner %d reconnected (breaker closed)", owner)

    def _note_failure(self, owner: int) -> None:
        br = self._breaker(owner)
        was_open = br.is_open
        br.record_failure()
        refresh = False
        with self._lock:
            n = self._consec_failures.get(owner, 0) + 1
            self._consec_failures[owner] = n
            if n % self._refresh_after == 0:
                refresh = True
            open_now = sum(1 for b in self._breakers.values() if b.is_open)
        _BREAKER_OPEN.set(open_now)
        if br.is_open and not was_open:
            _BREAKER_TRIPS.inc()
        if refresh and hasattr(self._inner, "refresh_channel"):
            # wedge recovery: a channel that failed refresh_after times
            # in a row gets fresh sockets instead of trust
            self._inner.refresh_channel(owner)

    def _backoff(self, attempt: int) -> float:
        cap = min(self._backoff_max_s,
                  self._backoff_base_s * (2 ** attempt))
        return cap * self._rng.uniform(0.1, 1.0)

    def _kw(self, timeout_s: Optional[float]) -> Dict[str, Any]:
        return ({"timeout_s": timeout_s}
                if self._inner_takes_deadline and timeout_s is not None
                else {})

    def _hedge_pool(self) -> ThreadPoolExecutor:
        with self._pool_lock:
            if self._pool is None:
                # sized above the worst transient: abandoned primary
                # calls against a blackholed owner occupy slots until
                # their wire deadline, and the breaker needs a few
                # losses before it stops submitting them
                self._pool = ThreadPoolExecutor(
                    max_workers=8, thread_name_prefix="emb-hedge")
            return self._pool

    def hedge_delay_s(self) -> float:
        """The delay before a pull hedges: pinned by config, or derived
        as the p99 of recent successful primary pulls (docs/
        performance.md \"Hedge-delay sizing\") with a floor — hedging
        the median would double read traffic for nothing; hedging only
        past p99 spends <1% extra reads to cut the tail."""
        if self._hedge_delay_ms > 0:
            delay = self._hedge_delay_ms / 1e3
        else:
            with self._lock:
                lats = sorted(self._pull_lat)
            if not lats:
                delay = 0.05
            else:
                # 1.25x p99: past p99 the primary has already missed
                # its tail SLO, and the margin only delays the rescue —
                # <1% of reads pay the extra replica call either way
                delay = max(HEDGE_FLOOR_MS / 1e3,
                            quantile_sorted(lats, 0.99) * 1.25)
        _HEDGE_DELAY_MS.set(round(delay * 1e3, 3))
        return delay

    def _replicas_of(self, shard: int, exclude: int) -> List[int]:
        if self._view_fn is None:
            return []
        try:
            view = self._view_fn()
        except Exception:
            # the view source is advisory for hedging; a failing fetch
            # just means no hedge this round: edl-lint: disable=EDL303
            return []
        if view is None:
            return []
        return [r for r in view.replicas_of(shard) if r != exclude]

    # ---- pull: deadline budget + hedge + degraded ladder ----------- #

    def pull(self, owner: int, table: str, shard: int,
             local_ids: np.ndarray, map_version: Optional[int] = None,
             with_watermark: bool = False, replica: bool = False):
        policy = self._policies["pull"]
        t_end = time.monotonic() + policy.budget_s
        if replica:
            # the tier's own replica-routing path: deadline + retry
            # only (a replica read hedging to another replica would
            # recurse); staleness judgment stays with the caller
            return self._retry_simple(
                "pull", policy, t_end, owner,
                lambda to: self._pull_once(
                    owner, table, shard, local_ids, map_version,
                    replica=True, timeout_s=to),
                with_watermark=with_watermark)
        rec = reqtrace.get_recorder()
        d = rec.start("pull", owner=int(owner), table=table,
                      shard=int(shard), tag=self._trace_tag)
        last: Optional[BaseException] = None
        try:
            for attempt in range(policy.max_attempts):
                remaining = t_end - time.monotonic()
                if remaining <= 0:
                    break
                _RPC_CALLS.inc(method="pull")
                try:
                    rows, wm = self._pull_round(
                        owner, table, shard, local_ids, map_version,
                        remaining, policy.max_attempts - attempt)
                    rec.finish(d, status=_diary_status(d))
                    return (rows, wm) if with_watermark else rows
                except StaleShardMapError:
                    raise
                except self.RETRYABLE as e:
                    last = e
                    _RPC_FAILURES.inc(method="pull")
                    if isinstance(e, DeadlineExceededError):
                        _RPC_DEADLINE.inc(method="pull")
                    if attempt + 1 < policy.max_attempts:
                        _RPC_RETRIES.inc(method="pull")
                        reqtrace.event("retry", attempt=attempt,
                                       error=type(e).__name__)
                        with reqtrace.stage("budget_wait"):
                            self._sleep(
                                min(self._backoff(attempt),
                                    max(0.0,
                                        t_end - time.monotonic())))
        except BaseException as e:
            rec.finish(d, status="error",
                       detail=f"{type(e).__name__}: {e}")
            raise
        # the ladder's last rung: no primary, no credible replica — the
        # read blocks (the caller's retry loop / deadline decides how
        # long). Counted so partitions can't hide inside retry loops.
        DEGRADED_READS.inc(mode="blocked")
        err = last if last is not None else DeadlineExceededError(
            f"pull {table}/{shard} from owner {owner}: deadline budget "
            f"({policy.budget_s:.3f}s) spent")
        rec.finish(d, status="error",
                   detail=f"{type(err).__name__}: {err}")
        raise err

    def _pull_once(self, owner: int, table: str, shard: int,
                   local_ids, map_version, replica: bool,
                   timeout_s: Optional[float]):
        """One wire attempt; breaker + latency + watermark bookkeeping."""
        t0 = time.perf_counter()
        try:
            rows, wm = self._inner.pull(
                owner, table, shard, local_ids, map_version=map_version,
                with_watermark=True, replica=replica,
                **self._kw(timeout_s))
        except StaleShardMapError:
            # an application answer on a healthy transport — the owner
            # is alive and talking; never a breaker strike
            self._note_success(owner)
            raise
        except self.RETRYABLE:
            self._note_failure(owner)
            raise
        self._note_success(owner)
        dt = time.perf_counter() - t0
        _RPC_LATENCY.observe(dt, method="pull")
        if not replica:
            with self._lock:
                self._pull_lat.append(dt)
        self._note_wm(table, shard, int(wm))
        self._maybe_drain(owner)
        return rows, int(wm)

    def _pull_round(self, owner: int, table: str, shard: int,
                    local_ids, map_version, remaining_s: float,
                    attempts_left: int):
        """One retry-loop round of the degraded ladder: primary (hedged
        past the hedge delay when a replica exists) -> replica-only when
        the breaker already says the primary is gone."""
        breaker = self._breaker(owner)
        reps = self._replicas_of(shard, exclude=owner)
        attempt_timeout = remaining_s / max(1, attempts_left)
        if not breaker.allow():
            # fail-fast rung: the primary is known-partitioned; a
            # credible replica serves (honestly counted), else this
            # round fails without burning wire time on a dead peer
            reqtrace.event("breaker_open", owner=int(owner))
            rows_wm = self._pull_replica_any(
                reps, table, shard, local_ids, map_version,
                attempt_timeout)
            if rows_wm is not None:
                DEGRADED_READS.inc(mode="replica")
                reqtrace.event("degraded", mode="replica")
                return rows_wm
            raise OwnerUnavailableError(
                f"owner {owner} breaker open and no credible replica "
                f"for {table}/{shard}")
        if not (self._hedge_enabled and reps):
            return self._pull_once(
                owner, table, shard, local_ids, map_version,
                replica=False, timeout_s=attempt_timeout)
        return self._pull_hedged(
            owner, reps, table, shard, local_ids, map_version,
            attempt_timeout)

    def _pull_replica_any(self, reps: List[int], table: str, shard: int,
                          local_ids, map_version,
                          timeout_s: float):
        """First credible replica answer, or None. Credible = within
        the staleness bound of the highest watermark this transport has
        observed for the shard — a partition must never become a
        license to serve arbitrarily stale rows. Two rounds over the
        replica set: a transient failure (an injected drop, one lost
        packet) on the ONLY replica must not sink the whole hedge —
        the primary it is rescuing is by definition already in
        trouble."""
        known = self.observed_wm(table, shard)
        for _ in range(2):
            for rep in reps:
                try:
                    rows, wm = self._pull_once(
                        rep, table, shard, local_ids, map_version,
                        replica=True, timeout_s=timeout_s)
                except (StaleShardMapError, *self.RETRYABLE):
                    continue
                if wm + self.staleness_bound >= known:
                    return rows, wm
        return None

    def _pull_hedged(self, owner: int, reps: List[int], table: str,
                     shard: int, local_ids, map_version,
                     timeout_s: float):
        return self._hedged_race(
            owner,
            lambda: self._pull_once(
                owner, table, shard, local_ids, map_version, False,
                timeout_s),
            lambda: self._pull_replica_any(
                reps, table, shard, local_ids, map_version, timeout_s),
            f"hedged pull {table}/{shard}: primary {owner} and "
            f"replicas {reps} all failed")

    def _hedged_race(self, owner: int, primary_call, hedge_call,
                     fail_msg: str):
        """Race the primary against a replica launched after the hedge
        delay; first credible answer wins, the loser is cancelled (or
        abandoned to its own deadline — gRPC has no mid-flight recall
        for a blocking call) and counted. `hedge_call` must return
        None (not raise) on failure; both the unary and the fused pull
        lanes race through here."""
        pool = self._hedge_pool()
        primary = pool.submit(primary_call)
        # the pre-hedge wait is attributed by how it RESOLVES: a primary
        # that answers inside the hedge window spent caller-side wire
        # time, one that forces the hedge spent the hedge DELAY — that
        # delay is the hedge mechanism's transient, and charging it to
        # `wire` would make a partition tail read as wire-bound. The
        # attempt runs on a pool thread (no diary there by design), so
        # the caller attributes its own wait either way.
        t0 = time.monotonic()
        done, _ = wait([primary], timeout=self.hedge_delay_s())
        reqtrace.attribute("wire" if done else "hedge",
                           time.monotonic() - t0)
        if done:
            return primary.result()   # fast path: no hedge launched
        _HEDGED.inc()
        reqtrace.event("hedge_fired", owner=int(owner))
        hedge = pool.submit(hedge_call)
        pending = {primary, hedge}
        primary_err: Optional[BaseException] = None
        while pending:
            with reqtrace.stage("hedge"):
                done, pending = wait(pending,
                                     return_when=FIRST_COMPLETED)
            for fut in done:
                if fut is primary:
                    try:
                        result = primary.result()
                    except (StaleShardMapError, *self.RETRYABLE) as e:
                        primary_err = e
                        continue
                    if hedge in pending and hedge.cancel():
                        pending.discard(hedge)
                    _HEDGE_CANCELLED.inc()
                    reqtrace.event("hedge_loss", owner=int(owner))
                    return result
                # hedge future: never raises (returns None on failure)
                rows_wm = fut.result()
                if rows_wm is not None:
                    _HEDGE_WINS.inc()
                    reqtrace.event("hedge_win", owner=int(owner))
                    if primary in pending:
                        # the primary call cannot be recalled mid-
                        # flight; it dies at its own wire deadline
                        primary.cancel()
                        pending.discard(primary)
                        _HEDGE_CANCELLED.inc()
                        # the primary did not answer inside the hedge
                        # window AND lost the race: attribute the read
                        DEGRADED_READS.inc(mode="replica")
                        reqtrace.event("degraded", mode="replica")
                        # a lost race is a missed SLO: strike the
                        # primary's breaker NOW rather than when its
                        # abandoned call times out — a partitioned
                        # owner must stop collecting hung calls (and
                        # hedge-pool slots) after a few losses, and a
                        # merely-slow owner's next on-time answer
                        # resets the count anyway
                        self._note_failure(owner)
                    elif primary_err is not None:
                        DEGRADED_READS.inc(mode="replica")
                        reqtrace.event("degraded", mode="replica")
                    return rows_wm
        if isinstance(primary_err, StaleShardMapError):
            raise primary_err
        raise primary_err if primary_err is not None else (
            OwnerUnavailableError(fail_msg))

    def _retry_simple(self, method: str, policy: CallPolicy,
                      t_end: float, owner: int, call,
                      with_watermark: bool = True):
        last: Optional[BaseException] = None
        for attempt in range(policy.max_attempts):
            remaining = t_end - time.monotonic()
            if remaining <= 0:
                break
            if not self._breaker(owner).allow():
                # fail fast against a known-partitioned owner: a
                # watermark probe or replica sync must not hang to its
                # deadline against a peer the breaker already condemned
                # (the caller's fallback — replica probes, deferred
                # sync — is the right response, and cheap)
                raise OwnerUnavailableError(
                    f"{method} to owner {owner}: breaker open")
            _RPC_CALLS.inc(method=method)
            try:
                rows, wm = call(
                    remaining / max(1, policy.max_attempts - attempt))
                return (rows, wm) if with_watermark else rows
            except StaleShardMapError:
                raise
            except self.RETRYABLE as e:
                last = e
                _RPC_FAILURES.inc(method=method)
                if isinstance(e, DeadlineExceededError):
                    _RPC_DEADLINE.inc(method=method)
                if attempt + 1 < policy.max_attempts:
                    _RPC_RETRIES.inc(method=method)
                    with reqtrace.stage("budget_wait"):
                        self._sleep(
                            min(self._backoff(attempt),
                                max(0.0, t_end - time.monotonic())))
        raise last if last is not None else DeadlineExceededError(
            f"{method} to owner {owner}: deadline budget spent")

    # ---- fused pull (ISSUE 18): one budget/hedge/breaker round per
    # fused call — the robustness machinery amortizes with the wire

    def supports_pull_multi(self) -> bool:
        return hasattr(self._inner, "pull_multi")

    def pull_multi(self, owner: int, requests,
                   map_version: Optional[int] = None,
                   replica: bool = False):
        """The fused LocalTransport.pull_multi contract with pull()'s
        full degraded ladder. The whole fused call gets ONE deadline
        budget, ONE hedge race, and ONE breaker verdict — n tables in
        a step no longer mean n chances to trip the breaker."""
        policy = self._policies["pull_multi"]
        t_end = time.monotonic() + policy.budget_s
        if replica:
            return self._retry_simple(
                "pull_multi", policy, t_end, owner,
                lambda to: self._pull_multi_once(
                    owner, requests, map_version, replica=True,
                    timeout_s=to),
                with_watermark=True)
        rec = reqtrace.get_recorder()
        d = rec.start("pull_multi", owner=int(owner),
                      fanin=len(requests), tag=self._trace_tag)
        last: Optional[BaseException] = None
        try:
            for attempt in range(policy.max_attempts):
                remaining = t_end - time.monotonic()
                if remaining <= 0:
                    break
                _RPC_CALLS.inc(method="pull_multi")
                try:
                    got = self._pull_multi_round(
                        owner, requests, map_version, remaining,
                        policy.max_attempts - attempt)
                    rec.finish(d, status=_diary_status(d))
                    return got
                except StaleShardMapError:
                    raise
                except self.RETRYABLE as e:
                    last = e
                    _RPC_FAILURES.inc(method="pull_multi")
                    if isinstance(e, DeadlineExceededError):
                        _RPC_DEADLINE.inc(method="pull_multi")
                    if attempt + 1 < policy.max_attempts:
                        _RPC_RETRIES.inc(method="pull_multi")
                        reqtrace.event("retry", attempt=attempt,
                                       error=type(e).__name__)
                        with reqtrace.stage("budget_wait"):
                            self._sleep(
                                min(self._backoff(attempt),
                                    max(0.0,
                                        t_end - time.monotonic())))
        except BaseException as e:
            rec.finish(d, status="error",
                       detail=f"{type(e).__name__}: {e}")
            raise
        DEGRADED_READS.inc(mode="blocked")
        err = last if last is not None else DeadlineExceededError(
            f"fused pull of {len(requests)} sub-pulls from owner "
            f"{owner}: deadline budget ({policy.budget_s:.3f}s) spent")
        rec.finish(d, status="error",
                   detail=f"{type(err).__name__}: {err}")
        raise err

    def _pull_multi_once(self, owner: int, requests, map_version,
                         replica: bool, timeout_s: Optional[float]):
        t0 = time.perf_counter()
        try:
            results, owner_wms = self._inner.pull_multi(
                owner, requests, map_version=map_version,
                replica=replica, **self._kw(timeout_s))
        except StaleShardMapError:
            self._note_success(owner)
            raise
        except self.RETRYABLE:
            self._note_failure(owner)
            raise
        self._note_success(owner)
        dt = time.perf_counter() - t0
        _RPC_LATENCY.observe(dt, method="pull_multi")
        if not replica:
            with self._lock:
                # ONE reservoir sample per FUSED call: the hedge delay
                # is p99-of-calls, and a fused call is one call — per
                # sub-table samples would multiply the window's weight
                # by the fan-in and self-inflate the derived delay as
                # coalescing grows
                self._pull_lat.append(dt)
            for (table, shard, _ids), (_rows, wm) in zip(requests,
                                                         results):
                self._note_wm(table, int(shard), int(wm))
        # the piggybacked watermarks are the OWNER'S primary set —
        # authoritative regardless of which namespace served this call
        for (table, shard), wm in owner_wms.items():
            self._note_wm(table, int(shard), int(wm))
        self._maybe_drain(owner)
        return results, owner_wms

    def _common_replicas(self, requests, exclude: int) -> List[int]:
        """Owners holding replicas of EVERY shard in the fused request
        — the only peers a fused call can hedge to wholesale."""
        common: Optional[set] = None
        for _t, shard, _ids in requests:
            reps = set(self._replicas_of(int(shard), exclude=exclude))
            common = reps if common is None else (common & reps)
            if not common:
                return []
        return sorted(common or ())

    def _pull_multi_replica_any(self, reps: List[int], requests,
                                map_version, timeout_s: float):
        """First replica owner whose fused answer is credible on EVERY
        sub-pull, or None. One stale sub-shard poisons the whole fused
        answer — partial acceptance would hand the tier a mix of fresh
        and beyond-bound rows under one watermark story."""
        for _ in range(2):
            for rep in reps:
                try:
                    results, owner_wms = self._pull_multi_once(
                        rep, requests, map_version, replica=True,
                        timeout_s=timeout_s)
                except (StaleShardMapError, *self.RETRYABLE):
                    continue
                credible = all(
                    wm + self.staleness_bound >= self.observed_wm(
                        table, int(shard))
                    for (table, shard, _ids), (_rows, wm)
                    in zip(requests, results)
                )
                if credible:
                    return results, owner_wms
        return None

    def _pull_multi_round(self, owner: int, requests, map_version,
                          remaining_s: float, attempts_left: int):
        breaker = self._breaker(owner)
        reps = self._common_replicas(requests, exclude=owner)
        attempt_timeout = remaining_s / max(1, attempts_left)
        if not breaker.allow():
            reqtrace.event("breaker_open", owner=int(owner))
            got = self._pull_multi_replica_any(
                reps, requests, map_version, attempt_timeout)
            if got is not None:
                DEGRADED_READS.inc(mode="replica")
                reqtrace.event("degraded", mode="replica")
                return got
            raise OwnerUnavailableError(
                f"owner {owner} breaker open and no credible replica "
                f"for fused pull of {len(requests)} sub-pulls")
        if not (self._hedge_enabled and reps):
            return self._pull_multi_once(
                owner, requests, map_version, replica=False,
                timeout_s=attempt_timeout)
        return self._hedged_race(
            owner,
            lambda: self._pull_multi_once(
                owner, requests, map_version, replica=False,
                timeout_s=attempt_timeout),
            lambda: self._pull_multi_replica_any(
                reps, requests, map_version, attempt_timeout),
            f"fused pull of {len(requests)} sub-pulls: primary "
            f"{owner} and replicas {reps} all failed")

    def watermark_multi(self, owner: int, pairs,
                        replica: bool = False) -> List[int]:
        """Batched freshness probe with shard_watermark()'s budget and
        breaker handling — one call per owner instead of one per
        (table, shard)."""
        policy = self._policies["watermark"]
        t_end = time.monotonic() + policy.budget_s

        def call(to):
            try:
                wms = self._inner.watermark_multi(
                    owner, pairs, replica=replica, **self._kw(to))
            except self.RETRYABLE:
                self._note_failure(owner)
                raise
            self._note_success(owner)
            return wms, 0

        wms, _ = self._retry_simple(
            "watermark", policy, t_end, owner, call)
        if not replica:
            for (table, shard), wm in zip(pairs, wms):
                self._note_wm(table, int(shard), int(wm))
        return [int(w) for w in wms]

    # ---- push: deadline budget + queue-behind-the-breaker ---------- #

    def push(self, owner: int, table: str, shard: int,
             local_ids: np.ndarray, rows: np.ndarray, *, client_id: str,
             seq: int, map_version: Optional[int] = None,
             scale: float = 1.0, with_watermark: bool = False):
        policy = self._policies["push"]
        t_end = time.monotonic() + policy.budget_s
        breaker = self._breaker(owner)
        rec = reqtrace.get_recorder()
        d = rec.start("push", owner=int(owner), table=table,
                      shard=int(shard), tag=self._trace_tag)
        try:
            # ORDER FENCE: while this owner has a backlog, every new
            # push must join the queue behind it (a later seq applied
            # before an earlier one would make the earlier drain a
            # swallowed duplicate). A healthy owner drains the backlog
            # first.
            if self.queue is not None and self.queue.depth(owner):
                if not (breaker.allow() and self._drain_owner(owner)):
                    got = self._enqueue_or_raise(
                        owner, table, shard, local_ids, rows,
                        client_id, seq, map_version, scale,
                        with_watermark)
                    rec.finish(d, status="degraded",
                               detail="queued behind owner backlog")
                    return got
            last: Optional[BaseException] = None
            for attempt in range(policy.max_attempts):
                remaining = t_end - time.monotonic()
                if remaining <= 0:
                    break
                if not breaker.allow():
                    reqtrace.event("breaker_open", owner=int(owner))
                    last = OwnerUnavailableError(
                        f"owner {owner} breaker open")
                    break
                _RPC_CALLS.inc(method="push")
                t0 = time.perf_counter()
                try:
                    applied, wm = self._inner.push(
                        owner, table, shard, local_ids, rows,
                        client_id=client_id, seq=seq,
                        map_version=map_version, scale=scale,
                        with_watermark=True,
                        **self._kw(
                            remaining
                            / max(1, policy.max_attempts - attempt)))
                except StaleShardMapError:
                    self._note_success(owner)
                    raise
                except self.RETRYABLE as e:
                    last = e
                    self._note_failure(owner)
                    _RPC_FAILURES.inc(method="push")
                    if isinstance(e, DeadlineExceededError):
                        _RPC_DEADLINE.inc(method="push")
                    if attempt + 1 < policy.max_attempts:
                        _RPC_RETRIES.inc(method="push")
                        reqtrace.event("retry", attempt=attempt,
                                       error=type(e).__name__)
                        # SAME seq on the re-send: an ambiguous
                        # failure (the owner may have applied before
                        # the reply was lost) is absorbed by the
                        # store's fence
                        with reqtrace.stage("budget_wait"):
                            self._sleep(
                                min(self._backoff(attempt),
                                    max(0.0,
                                        t_end - time.monotonic())))
                    continue
                self._note_success(owner)
                _RPC_LATENCY.observe(time.perf_counter() - t0,
                                     method="push")
                self._note_wm(table, shard, int(wm))
                rec.finish(d, status="ok")
                return (applied, int(wm)) if with_watermark else applied
            # the breaker rung: park the push durably instead of
            # blocking the training step for the whole partition
            if self.queue is not None:
                got = self._enqueue_or_raise(
                    owner, table, shard, local_ids, rows, client_id,
                    seq, map_version, scale, with_watermark)
                rec.finish(d, status="degraded",
                           detail="queued behind open breaker")
                return got
            err = last if last is not None else DeadlineExceededError(
                f"push {table}/{shard} seq {seq}: deadline budget "
                f"spent")
            raise err
        except BaseException as e:
            rec.finish(d, status="error",
                       detail=f"{type(e).__name__}: {e}")
            raise

    def _enqueue_or_raise(self, owner, table, shard, local_ids, rows,
                          client_id, seq, map_version, scale,
                          with_watermark):
        entry = {
            "owner": int(owner), "table": table, "shard": int(shard),
            "ids": np.array(local_ids, np.int32, copy=True),
            "rows": np.array(rows, np.float32, copy=True),
            "client_id": client_id, "seq": int(seq),
            "map_version": map_version, "scale": float(scale),
        }
        if not self.queue.enqueue(entry):
            raise OwnerUnavailableError(
                f"owner {owner} partitioned and the push queue is full "
                f"({self.queue.max_entries}); refusing to buffer "
                "unboundedly")
        logger.warning(
            "push %s/%d seq %d queued behind owner %d's open breaker "
            "(%d parked)", table, shard, seq, owner,
            self.queue.depth(owner))
        # the ack is honest about what happened: applied=False (nothing
        # landed yet) with the highest watermark this client has seen —
        # the tier's write-through check (new_wm == prev_wm + 1) then
        # drops rather than patches, and the caller's training step
        # continues instead of blocking for the partition's duration
        wm = self.observed_wm(table, shard)
        return (False, wm) if with_watermark else False

    def _maybe_drain(self, owner: int) -> None:
        if self.queue is not None and self.queue.depth(owner):
            self._drain_owner(owner)

    def drain_queued(self, owner: Optional[int] = None) -> int:
        """Explicit reconnect drain (worker task boundaries, bench
        heal). Returns how many queued pushes landed."""
        if self.queue is None:
            return 0
        owners = ([owner] if owner is not None
                  else self.queue.owners_with_backlog())
        drained = 0
        for o in owners:
            before = self.queue.depth(o)
            self._drain_owner(o)
            drained += before - self.queue.depth(o)
        return drained

    def _drain_owner(self, owner: int) -> bool:
        """Re-send the owner's parked pushes in enqueue order under
        their ORIGINAL seqs (the fence absorbs any that actually
        landed before their ack was lost). Stops at the first failure
        — order is the contract. True = backlog fully drained."""
        if self.queue is None:
            return True
        with self._drain_lock:
            while True:
                entry = self.queue.peek(owner)
                if entry is None:
                    return True
                target = owner
                map_version = entry["map_version"]
                try:
                    self._inner.push(
                        target, entry["table"], entry["shard"],
                        entry["ids"], entry["rows"],
                        client_id=entry["client_id"], seq=entry["seq"],
                        map_version=map_version, scale=entry["scale"],
                        with_watermark=True,
                        **self._kw(self._policies["push"].budget_s))
                except StaleShardMapError:
                    # the map moved during the partition: re-route to
                    # the shard's CURRENT owner, version un-pinned (the
                    # store's residency check still protects us)
                    routed = self._reroute(entry)
                    if not routed:
                        return False
                except self.RETRYABLE:
                    self._note_failure(owner)
                    return False
                else:
                    self._note_success(owner)
                self.queue.pop_drained(owner)
                logger.debug(
                    "drained queued push %s/%d seq %d to owner %d",
                    entry["table"], entry["shard"], entry["seq"], target)

    def _reroute(self, entry: Dict[str, Any]) -> bool:
        if self._view_fn is None:
            return False
        try:
            view = self._view_fn()
            target = view.owner_of(int(entry["shard"]))
            self._inner.push(
                target, entry["table"], entry["shard"], entry["ids"],
                entry["rows"], client_id=entry["client_id"],
                seq=entry["seq"], map_version=None,
                scale=entry["scale"], with_watermark=True,
                **self._kw(self._policies["push"].budget_s))
            return True
        except (StaleShardMapError, *self.RETRYABLE):
            return False

    # ---- the rest of the contract: budgeted pass-through ----------- #

    def fetch_shard(self, owner: int, table: str,
                    shard: int) -> Dict[str, Any]:
        policy = self._policies["fetch_shard"]
        t_end = time.monotonic() + policy.budget_s

        def call(to):
            try:
                payload = self._inner.fetch_shard(
                    owner, table, shard, **self._kw(to))
            except self.RETRYABLE:
                self._note_failure(owner)
                raise
            self._note_success(owner)
            return payload, int(payload.get("wm", 0))

        payload, _ = self._retry_simple(
            "fetch_shard", policy, t_end, owner, call)
        return payload

    def fetch_delta(self, owner: int, table: str, shard: int,
                    since_wm: int) -> Optional[Dict[str, Any]]:
        policy = self._policies["fetch_delta"]
        t_end = time.monotonic() + policy.budget_s

        def call(to):
            try:
                delta = self._inner.fetch_delta(
                    owner, table, shard, since_wm, **self._kw(to))
            except self.RETRYABLE:
                self._note_failure(owner)
                raise
            self._note_success(owner)
            return delta, (int(delta["wm"]) if delta else 0)

        delta, _ = self._retry_simple(
            "fetch_delta", policy, t_end, owner, call)
        return delta

    def shard_watermark(self, owner: int, table: str, shard: int,
                        replica: bool = False) -> int:
        policy = self._policies["watermark"]
        t_end = time.monotonic() + policy.budget_s

        def call(to):
            try:
                wm = self._inner.shard_watermark(
                    owner, table, shard, replica=replica,
                    **self._kw(to))
            except self.RETRYABLE:
                self._note_failure(owner)
                raise
            self._note_success(owner)
            return int(wm), int(wm)

        wm, _ = self._retry_simple(
            "watermark", policy, t_end, owner, call)
        if not replica:
            self._note_wm(table, shard, wm)
        return wm

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None
        if hasattr(self._inner, "close"):
            self._inner.close()


# ------------------------------------------------------------------ #
# standalone owner runner (the multi-process half of bench.py
# data_plane): serve a store built from a JSON spec, optionally keep
# replica copies synced from their primaries, write the bound port to
# a file the orchestrator watches.


def _runner_view(spec: Dict[str, Any]):
    from elasticdl_tpu.embedding import sharding

    tables = tuple(
        sharding.TableSpec(
            name=t["name"], vocab=int(t["vocab"]), dim=int(t["dim"]),
            seed=int(t.get("seed", 0)),
            init_scale=float(t.get("init_scale", 0.05)),
        )
        for t in spec["tables"]
    )
    return sharding.ShardMapView(
        version=int(spec.get("version", 1)),
        num_shards=int(spec["num_shards"]),
        owners=tuple(int(o) for o in spec["owners"]),
        tables=tables,
        replicas=tuple(tuple(int(x) for x in r)
                       for r in spec.get("replicas", [])),
    )


def run_owner(spec: Dict[str, Any], stop: Optional[threading.Event] = None):
    """Serve one owner process per the spec (see bench.py data_plane
    for the producing side). Blocks until `stop` (or SIGTERM)."""
    from elasticdl_tpu.embedding.store import EmbeddingShardStore

    owner = int(spec["owner"])
    view = _runner_view(spec)
    store = EmbeddingShardStore(owner, device=bool(spec.get("device")))
    store.attach(view)
    server = EmbeddingDataServer(store, shm=bool(spec.get("shm", True)))
    port = server.start(int(spec.get("port", 0)))
    port_file = spec.get("port_file")
    if port_file:
        tmp = port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(port))
        os.replace(tmp, port_file)
    stop = stop or threading.Event()

    # replica-sync loop: this owner holds replica copies of shards
    # whose primaries live at peer_addrs — keep them fresh by delta so
    # a partitioned primary's clients can hedge here
    my_replicas = [
        s for s in range(view.num_shards)
        if owner in view.replicas_of(s)
    ]
    sync_s = float(spec.get("replica_sync_s", 0.05))
    peer = GrpcTransport(
        {int(k): v for k, v in (spec.get("peer_addrs") or {}).items()})

    def sync_loop():
        while not stop.is_set():
            for s in my_replicas:
                for t in view.tables:
                    try:
                        store.sync_replica_from(
                            peer, view.owner_of(s), t.name, s)
                    except Exception:
                        logger.debug(
                            "replica sync %s/%d deferred", t.name, s,
                            exc_info=True)
            stop.wait(sync_s)

    if my_replicas:
        threading.Thread(
            target=sync_loop, name="emb-replica-sync", daemon=True
        ).start()
    try:
        stop.wait()
    finally:
        server.stop()
    return port


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import signal

    parser = argparse.ArgumentParser(
        description="standalone embedding data-plane owner process")
    parser.add_argument("--serve", metavar="SPEC_JSON", required=True,
                        help="owner spec file (bench.py data_plane "
                        "writes these)")
    args = parser.parse_args(argv)
    with open(args.serve) as f:
        spec = json.load(f)
    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    run_owner(spec, stop)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
