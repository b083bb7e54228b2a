"""A worker process's session with the master: how it stays known to a
master that may restart, lapse or be gone.

The plain `Worker` (worker/worker.py) and the cohort's leader
(worker/cohort.py — followers never talk to the master) each hold one
`MasterSession`: the channel and the hardened stub, the registered name
and id, the master-unreachable clock, the reconnect handshake and the
heartbeat loop. What differs between the two comes in from the owner.
`job_checkpoint_manager` is here because both need it and nothing else does.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional

from elasticdl_tpu.common import faults
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.common.log_utils import default_logger
from elasticdl_tpu.observability import goodput as goodput_lib
from elasticdl_tpu.observability import profile as profile_lib
from elasticdl_tpu.observability import reqtrace as reqtrace_lib
from elasticdl_tpu.observability import timeseries as timeseries_lib
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.health import STATS_METADATA_KEY, encode_stats
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.proto.service import (
    RetryingMasterStub,
    is_stale_generation,
    jittered,
    make_channel,
    register_with_retry,
    reregister,
)

logger = default_logger(__name__)


def job_checkpoint_manager(cfg: JobConfig):
    """The job's CheckpointManager, or None without a `checkpoint_dir`
    (orbax is imported only by a job that checkpoints)."""
    if not cfg.checkpoint_dir:
        return None
    from elasticdl_tpu.training.checkpoint import CheckpointManager

    return CheckpointManager(cfg.checkpoint_dir, keep=cfg.keep_checkpoint_max)


class MasterSession:
    """`shutdown` is the OWNER's event: the session sets it (the master
    said so, or is lost) and its loops end on it. `what` names the owner in
    log lines and `when_lost` says what a lost master does to it.
    `on_reregistered(resp)` applies a reconnect handshake's response to the
    owner's state, on whichever thread ran the handshake."""

    def __init__(self, cfg: JobConfig, shutdown: threading.Event, *,
                 what: str, when_lost: str,
                 on_reregistered: Callable[[Any], None]):
        self.cfg = cfg
        self._shutdown = shutdown
        self._what = what
        self._when_lost = when_lost
        self._on_reregistered = on_reregistered
        self.stub: Optional[RetryingMasterStub] = None
        self._channel = None
        self.name = ""                # set at registration
        self.worker_id = -1
        # registered once, reused by every reconnect handshake: a renamed
        # re-register would silently overwrite the membership entry's name
        self._register_fields: Dict[str, Any] = {}
        self.last_master_ok = time.monotonic()  # last successful master RPC
        self.master_lost = False      # unreachable past the config timeout
        # what the master said, by heartbeat or by lease: the job finished
        # (export the final model) / checkpoint at the next task boundary
        self.job_done = False
        self.checkpoint_requested = False
        self._heartbeat_thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------------ #
    # registration and the reconnect handshake

    def connect(self, name: str, preferred_id: int, **register_fields):
        """Open the channel and register, riding out a master that is down
        or restarting (proto/service.py's register_with_retry); returns the
        response. `register_fields` go into every later re-register too."""
        addr = self.cfg.master_addr
        self._channel = make_channel(addr)
        # Hardened stub: per-call deadlines, idempotent-only retries with
        # backoff, circuit breaker. Every successful RPC (on any thread)
        # refreshes the master-unreachable clock through on_success. The
        # channel_factory makes master-restart recovery bounded: repeated
        # transport failures rebuild the channel instead of trusting a
        # subchannel that got stuck when the old master's listener vanished.
        self.stub = RetryingMasterStub(
            self._channel, on_success=self._note_master_ok,
            channel_factory=lambda: make_channel(addr),
        )
        self.name = name
        self._register_fields = register_fields
        resp = register_with_retry(
            self.stub, name=name, preferred_id=preferred_id,
            window_s=self.cfg.master_unreachable_timeout_s,
            shutdown=self._shutdown, what=self._what, **register_fields,
        )
        self.worker_id = resp.worker_id
        return resp

    def _note_master_ok(self) -> None:
        """RetryingMasterStub success hook (runs on whichever thread made
        the call): the master answered, so the unreachable clock resets."""
        self.last_master_ok = time.monotonic()

    def master_unreachable(self) -> bool:
        """Called from RPC-failure paths: True (once; also flips
        master_lost and the owner's shutdown) when no master RPC has
        succeeded for master_unreachable_timeout_s — the master is
        permanently gone, and retrying forever would leave an orphan
        process spinning on a dead address (observed: cohort members
        surviving hours after their master's process tree was killed). The
        owner exits EX_TEMPFAIL instead: a live manager relaunches it; an
        orphan frees its chip and memory."""
        limit = self.cfg.master_unreachable_timeout_s
        if limit <= 0 or time.monotonic() - self.last_master_ok < limit:
            return False
        if not self.master_lost:
            self.master_lost = True
            logger.error(
                "no successful master RPC for %.0fs (limit %.0fs): master "
                "presumed gone, %s",
                time.monotonic() - self.last_master_ok, limit,
                self._when_lost,
            )
            self._shutdown.set()
        return True

    def reregister(self) -> None:
        """The reconnect handshake (proto/service.py's reregister):
        idempotent re-register under our EXISTING worker id and name, after
        a master restart or after this master's reaper wrote a live worker
        off; then the owner applies the response."""
        resp = reregister(
            self.stub, name=self.name, worker_id=self.worker_id,
            **self._register_fields,
        )
        self.worker_id = resp.worker_id
        self._on_reregistered(resp)

    def maybe_reconnect(self, e: BaseException) -> bool:
        """RPC-failure triage for the master's fence: True when `e` was a
        stale-generation rejection AND the reconnect handshake ran — the
        caller should retry its loop instead of backing off or dying. Any
        other error (including a failed re-register: the master may have
        crashed AGAIN mid-handshake) returns False and leaves the normal
        unreachable accounting to the caller."""
        if self.worker_id < 0 or not is_stale_generation(e):
            return False
        try:
            self.reregister()
            return True
        except Exception as handshake_err:
            logger.warning(
                "%s re-register after master restart failed: %s",
                self._what, handshake_err,
            )
            self.master_unreachable()
            return False

    # ------------------------------------------------------------------ #
    # heartbeats

    def stats_ride_alongs(self, tier=None) -> Dict[str, Any]:
        """What every heartbeat payload carries besides its owner's step
        window and phase (a cohort's is its leader's own: followers'
        ledgers and diaries stay process-local)."""
        stats: Dict[str, Any] = dict(
            breaker_open=int(bool(self.stub and self.stub.breaker.is_open)),
            world_version=tracing.get_tracer().world_version,
        )
        # step-profiler phase breakdown + memory watermarks (bounded key
        # set): the master's ClusterHealth sees WHY a straggler is slow
        stats.update(profile_lib.get_profiler().snapshot())
        # goodput ledger ride-along (ISSUE 12): cumulative per-category
        # wall-clock attribution (gp_* keys) — the master's FleetGoodput
        # rollup totals these into the fleet goodput fraction
        stats.update(goodput_lib.get_ledger().payload())
        # request-diary ride-along (ISSUE 19): compact tail-attribution
        # rollup (rt_* keys) + degraded/shm-fallback shares — the
        # master's FleetAttribution and fleet_series read these
        stats.update(reqtrace_lib.get_recorder().payload())
        # embedding-tier skew ride-along (ISSUE 11): hot-id share, shard
        # imbalance, recent pull/push p99 — the fleet rollup's sensor for
        # the hot-row-cache decision. Best-effort like the rest of the
        # payload: a tier hiccup must never cost the heartbeat.
        if tier is not None:
            try:
                stats.update(tier.client.tier_stats())
            except Exception:
                # edl-lint: disable=EDL303
                pass
        return stats

    def start_heartbeats(self, **loop_args) -> None:
        """`heartbeat_loop(**loop_args)` on a daemon thread; `close` joins."""
        self._heartbeat_thread = threading.Thread(
            target=self.heartbeat_loop, kwargs=loop_args, daemon=True
        )
        self._heartbeat_thread.start()

    def heartbeat_loop(
        self, *,
        model_version: Callable[[], int],
        stats_payload: Callable[[], Dict[str, Any]],
        on_response: Callable[[Any], None],
        request_fields: Callable[[], Dict[str, Any]] = dict,
        fault_point: Optional[str] = None,
    ) -> None:
        """Beat until shutdown. `model_version()` reads the owner's
        plain-int mirror, never the device; `request_fields()` adds the
        owner's fields to the request (a cohort's coalesced member beats);
        `on_response(resp)` sees every response that did not end the
        session."""
        while not self._shutdown.is_set():
            # time-series sample when due (interval-gated: normally one
            # clock read per beat); rides the heartbeat thread so the
            # train loop never pays for a registry snapshot
            timeseries_lib.get_store().maybe_sample()
            try:
                if fault_point:
                    # chaos hook: <fault_point>:crash kills the process
                    # here (a hard worker death between task boundaries);
                    # drop/delay fall through the same except path as a
                    # network failure
                    faults.fire(fault_point)
                # telemetry rides as OPTIONAL metadata: a master that does
                # not understand it ignores it, and a payload-building
                # failure degrades this beat to liveness-only — stats must
                # never cost a heartbeat
                try:
                    md = ((STATS_METADATA_KEY,
                           encode_stats(stats_payload())),)
                except Exception:
                    md = None
                resp = self.stub.Heartbeat(
                    pb.HeartbeatRequest(
                        worker_id=self.worker_id,
                        model_version=model_version(),
                        **request_fields(),
                    ),
                    timeout=10,
                    metadata=md,
                )
                if resp.shutdown:
                    logger.info("master requested shutdown")
                    # job_done distinguishes normal completion (export the
                    # final model) from aborts/evictions (don't)
                    if resp.job_done:
                        self.job_done = True
                    self._shutdown.set()
                    break
                if resp.should_checkpoint:
                    # honored by the owner at the next task boundary (the
                    # heartbeat thread must not save mid-train-step)
                    self.checkpoint_requested = True
                on_response(resp)
            except Exception as e:
                logger.warning("%s heartbeat failed: %s", self._what, e)
                # a stale-generation fence means the master is THERE (it
                # restarted, or wrote us off); re-register instead of
                # counting it toward the unreachable exit
                if not self.maybe_reconnect(e):
                    self.master_unreachable()
            # jittered beat: a synchronized swarm (mass relaunch, master
            # restart) must de-phase instead of arriving as one herd
            self._shutdown.wait(jittered(self.cfg.worker_heartbeat_s))

    def close(self) -> None:
        """Orderly teardown, after the owner set its shutdown: stop the
        heartbeat thread and close the channel BEFORE interpreter exit — a
        grpc call in flight during shutdown aborts the process from the
        C++ layer."""
        if self._heartbeat_thread is not None:
            self._heartbeat_thread.join(
                timeout=2 * self.cfg.worker_heartbeat_s)
        if self._channel is None:
            return
        try:
            self._channel.close()
        except Exception:
            # teardown-only: the process is exiting either way, but the
            # failure is still worth a debug line for post-mortems
            logger.debug("grpc channel close failed at exit", exc_info=True)
