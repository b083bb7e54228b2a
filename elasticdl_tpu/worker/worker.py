"""The worker: lease tasks from the master, run the jitted step, report back.

Reference parity: elasticdl/python/worker/worker.py — `Worker.run()` loops
`get_task` → build dataset → per-minibatch train step → `report_task_result`,
plus evaluation and prediction task handling. The hot path differs exactly as
SURVEY §3.3 prescribes: no per-step PS pulls/pushes — forward, backward, and
optimizer update are one donated-state XLA program on the local mesh, and the
only RPCs left are one lease + one report per task plus heartbeats.
"""

from __future__ import annotations

import contextlib
import os
import socket
import threading
import time
from collections import deque
from typing import Any, Dict, Optional

import numpy as np

from elasticdl_tpu.common import faults, membership_signal
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.common.constants import WorkerEnv
from elasticdl_tpu.common.log_utils import default_logger
from elasticdl_tpu.data.reader import create_data_reader
from elasticdl_tpu.observability import flight as flight_lib
from elasticdl_tpu.observability import goodput as goodput_lib
from elasticdl_tpu.observability import profile as profile_lib
from elasticdl_tpu.observability import timeseries as timeseries_lib
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.health import WorkerStepStats
from elasticdl_tpu.observability.registry import default_registry
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.proto.service import is_stale_generation, jittered
from elasticdl_tpu.training.model_spec import ModelSpec
from elasticdl_tpu.worker.session import MasterSession, job_checkpoint_manager
from elasticdl_tpu.worker.task_data_service import TaskDataService

logger = default_logger(__name__)

_reg = default_registry()
_TRAIN_STEPS = _reg.counter(
    "edl_train_steps_total", "train steps run by this worker")
_TRAIN_RECORDS = _reg.counter(
    "edl_train_records_total", "non-padding records applied")
_TRAIN_THROUGHPUT = _reg.gauge(
    "edl_train_samples_per_sec",
    "per-task mean throughput (records / measured step wall time)")
_TRAIN_STEP_S = _reg.histogram(
    "edl_train_step_seconds", "per-step wall time (dispatch + compute)")
_RESCALES = _reg.counter(
    "edl_rescale_applied_total", "in-place rescales applied")
_RESCALE_S = _reg.histogram(
    "edl_rescale_seconds", "in-place rescale recovery wall time")
_RECONNECTS = _reg.counter(
    "edl_worker_reconnects_total",
    "reconnect handshakes after a master restart (re-register + re-lease)")


class Worker:
    def __init__(self, cfg: JobConfig, mesh=None):
        self.cfg = cfg
        self._mesh = mesh
        self._trainer = None
        self._state = None
        self._spec: Optional[ModelSpec] = None
        self._services: Dict[int, TaskDataService] = {}
        self._membership_version = -1
        self._shutdown = threading.Event()
        # the channel, the stub, our id and the liveness protocol
        self._session = MasterSession(
            cfg, self._shutdown, what="worker",
            when_lost="exiting EX_TEMPFAIL",
            on_reregistered=self._on_reregistered,
        )
        self._parse_fns: Dict[str, Any] = {}
        self._ckpt_manager = None
        self._last_ckpt_step = 0
        self._preempted = False
        self._mid_training_task = False
        self._base_lr = None          # injected LR at init (elastic scaling)
        self._pending_lr = None       # set by heartbeat thread, applied by run loop
        self._pushed_lr = 0.0         # last master-pushed LR override seen
        self._last_known_workers = 0  # latest alive count (register/heartbeat)
        self._global_step = 0         # train steps run by this worker
        # Plain-int mirror of state.model_version, maintained by the MAIN
        # thread at state creation/restore and after each step/group. The
        # heartbeat thread must read THIS, never state.model_version:
        # int(state.step) blocks on the in-flight donated computation, so a
        # multi-second dispatch (train_many groups, big compiles) would
        # silently stall heartbeats until the master declares us dead.
        self._model_version = 0
        self._profile_state = "idle"  # idle -> active -> done (jax.profiler)
        # In-place rescale (rescale fast path): a pending (axis_sizes,
        # devices) target applied at the next batch/task boundary — live
        # state handoff + executable-cache reuse, no teardown/restore.
        self._pending_rescale = None
        self.last_recovery_s: Optional[float] = None
        # heartbeat-piggybacked telemetry (observability/health.py): the
        # train loop observes step timings, the heartbeat thread snapshots
        # them into the stats payload the master's straggler scorer reads
        self._step_stats = WorkerStepStats()
        self._rescaling = False       # True while _rescale_in_place runs
        # Batched leases (--task_lease_batch): locally leased tasks still
        # to run — drained before the next GetTask poll. Cleared on every
        # reconnect handshake: a restarted master's replay requeued these
        # leases whole, so running a local copy would be wasted work (its
        # report comes back accepted=False either way).
        self._lease_queue: "deque[pb.Task]" = deque()
        # Elastic sharded embedding tier (cfg.embedding_shards > 0): this
        # worker's owning store + pull/push client. Membership bumps set
        # the refresh flag (heartbeat thread); the run loop reacts at the
        # next task boundary — shard installs must not stall heartbeats.
        self._tier = None
        self._tier_refresh_pending = False

    # ------------------------------------------------------------------ #
    # setup

    @property
    def worker_id(self) -> int:
        return self._session.worker_id

    def _connect(self) -> None:
        # gRPC embedding data plane (ISSUE 15): the endpoint comes up
        # BEFORE registration so its address can ride the RegisterWorker
        # request into the owner address book; the store binds later,
        # when the tier runtime builds it (_init_embedding_tier)
        self._start_data_plane()
        resp = self._session.connect(
            f"{socket.gethostname()}:{os.getpid()}",
            int(os.environ.get(WorkerEnv.WORKER_ID, -1)),
            data_addr=self._data_addr,
        )
        self._membership_version = resp.membership_version
        self._last_known_workers = resp.num_workers
        # role known now: trace spans + JSON logs carry it; a reform trace
        # id announced by the master (membership signal) makes this boot
        # part of the resize's cross-role timeline
        tracing.configure_from_config(
            self.cfg, role=f"worker-{self.worker_id}"
        )
        # flight recorder: the black box dumps on crash/SIGUSR2/endpoint
        # (observability/flight.py trigger matrix); armed as soon as the
        # role is known so even boot failures leave a bundle
        flight_lib.configure_from_config(
            self.cfg, role=f"worker-{self.worker_id}"
        )
        flight_lib.install_crash_hooks()
        # metrics time series (observability/timeseries.py): the process
        # ring behind GET /timeseries + rolling metrics_history.jsonl;
        # sampled from the heartbeat loop (the interval gate makes the
        # per-beat cost a clock read)
        timeseries_lib.configure_from_config(
            self.cfg, role=f"worker-{self.worker_id}"
        )
        logger.info(
            "registered as worker %d (membership v%d, %d workers)",
            self.worker_id, resp.membership_version, resp.num_workers,
        )

    def _start_data_plane(self) -> None:
        """Bind the per-worker EmbeddingData endpoint (next to the
        observability endpoint — both are sidecar servers on daemon
        threads). A bind failure is fatal: `--embedding_transport grpc`
        means peers' shards live in other processes, so silently
        falling back to LocalTransport would leave every peer-owned
        pull/push raising OwnerUnavailableError forever (and peers
        unable to reach our shards) — fail at boot, loudly, instead."""
        self._data_server = None
        if (self.cfg.embedding_shards <= 0
                or self.cfg.embedding_transport != "grpc"):
            return
        try:
            from elasticdl_tpu.embedding.data_plane import (
                EmbeddingDataServer,
            )

            self._data_server = EmbeddingDataServer(
                shm=self.cfg.embedding_shm)
            self._data_server.start()
        except Exception as e:
            self._data_server = None
            raise RuntimeError(
                "embedding data-plane endpoint failed to start but "
                "--embedding_transport grpc requires it (peer-owned "
                f"shards are unreachable over LocalTransport): {e}"
            ) from e

    @property
    def _data_addr(self) -> str:
        srv = getattr(self, "_data_server", None)
        return srv.address or "" if srv is not None else ""

    def _on_reregistered(self, resp) -> None:
        """A reconnect handshake landed (worker/session.py): apply its
        response."""
        # drop locally queued leases: the master conservatively requeued
        # every lease of the dead generation (or of the worker it wrote
        # off), so these tasks will re-run (exactly once) through fresh
        # leases
        self._lease_queue.clear()
        self._membership_version = resp.membership_version
        self._last_known_workers = resp.num_workers or self._last_known_workers
        _RECONNECTS.inc()
        tracing.event(
            "worker.reconnect", worker_id=self.worker_id,
            membership_version=resp.membership_version,
        )
        logger.warning(
            "re-registered with restarted master as worker %d "
            "(membership v%d); resuming leases under the new generation",
            self.worker_id, resp.membership_version,
        )

    def _build_trainer(self) -> None:
        from elasticdl_tpu.common.runtime import (
            configure_jax_runtime,
            log_training_devices,
        )
        from elasticdl_tpu.parallel.mesh import build_job_mesh
        import jax

        # reaching the chips (18 s for four, PR 21) apart from building the
        # model: two spans of the start-up ledger
        with tracing.start_span("backend"):
            configure_jax_runtime(self.cfg)
            if self._mesh is None:
                self._mesh = build_job_mesh(self.cfg, jax.devices())
            log_training_devices(self._mesh)
        with tracing.start_span("trainer"):
            self._spec = ModelSpec.from_config(self.cfg)
            self._trainer = self._make_trainer(self._mesh)

    def _make_trainer(self, mesh):
        """One Trainer construction path for boot AND in-place rescale: the
        config-derived cache token is what lets the post-rescale trainer
        find the speculatively-compiled executables (compile_cache.py)."""
        from elasticdl_tpu.training import compile_cache as cc
        from elasticdl_tpu.training.trainer import Trainer

        return Trainer(
            self._spec, mesh, remat=self.cfg.remat,
            remat_policy=self.cfg.remat_policy,
            grad_accum=self.cfg.grad_accum_steps, seed=self.cfg.shuffle_seed,
            cache_token=cc.job_cache_token(self.cfg),
        )

    def _data_service(self, task_type: int) -> TaskDataService:
        if task_type not in self._services:
            paths = {
                pb.TRAINING: self.cfg.training_data,
                pb.EVALUATION: self.cfg.validation_data or self.cfg.training_data,
                pb.PREDICTION: self.cfg.prediction_data,
            }
            reader = create_data_reader(
                paths[task_type], self.cfg.data_reader, **self.cfg.data_reader_params
            )
            mode = {
                pb.TRAINING: "training",
                pb.EVALUATION: "evaluation",
                pb.PREDICTION: "prediction",
            }[task_type]
            if self._spec.dataset_fn is None:
                raise ValueError("model module must define dataset_fn for data tasks")
            parse = self._spec.dataset_fn(mode, reader.metadata)
            from elasticdl_tpu.parallel.mesh import data_axis

            multiple = dict(
                zip(self._mesh.axis_names, self._mesh.devices.shape)
            )[data_axis(self._mesh)]
            self._services[task_type] = TaskDataService(
                reader, parse, self.cfg.minibatch_size, batch_multiple=multiple
            )
        return self._services[task_type]

    def _prefetched(self, batches):
        """Overlap host->device transfer with compute (data/prefetch.py).
        Batches arrive pre-sharded, so the train step's shard_batch is a
        no-op for them. Depth/cast come from the config, overridable via
        EDL_PREFETCH_DEPTH / EDL_PREFETCH_CAST (env wins — operators tune
        the lookahead without touching the job's immutable argv)."""
        from elasticdl_tpu.data.prefetch import prefetch_to_device

        depth = (None if "EDL_PREFETCH_DEPTH" in os.environ
                 else self.cfg.prefetch_batches)
        cast = (None if "EDL_PREFETCH_CAST" in os.environ
                else self.cfg.wire_dtype)
        return prefetch_to_device(
            self._mesh, batches, depth, cast=cast,
            partition=self._spec.batch_partition if self._spec else None,
        )

    def _checkpoint_manager(self):
        if self._ckpt_manager is None:
            self._ckpt_manager = job_checkpoint_manager(self.cfg)
        return self._ckpt_manager

    def _ensure_state(self, example_batch: Dict[str, Any]) -> None:
        if self._state is not None:
            return
        self._state = self._trainer.init_state(example_batch)
        if self.cfg.scale_lr_with_workers and self._base_lr is None:
            from elasticdl_tpu.training.lr_modulation import get_learning_rate

            # Read the CONFIGURED base LR from the freshly-initialized state,
            # before checkpoint restore — a restored opt_state may already
            # carry an elastically scaled LR, and re-basing on it would
            # compound the scaling across relaunches.
            self._base_lr = get_learning_rate(self._state.opt_state)
            if self._base_lr is None:
                logger.warning(
                    "scale_lr_with_workers needs an optimizer built via "
                    "lr_modulation.modulated(...); LR scaling disabled"
                )
        # Elastic recovery: a relaunched worker resumes from the latest
        # checkpoint instead of fresh params (reference analog: rank-0
        # Horovod broadcast after re-rendezvous restoring replicated state).
        mngr = self._checkpoint_manager()
        if mngr is not None and mngr.latest_step() is not None:
            restored = mngr.restore(self._state)
            if restored is not None:
                self._state = restored
                self._last_ckpt_step = self._state.model_version
                logger.info(
                    "resumed from checkpoint at step %d", self._last_ckpt_step
                )
                if (self.cfg.scale_lr_with_workers and self._base_lr
                        and not self._pushed_lr):
                    from elasticdl_tpu.training.lr_modulation import linear_scale

                    # the restored opt_state may carry an LR scaled for a
                    # membership that no longer exists; re-derive it from the
                    # CURRENT worker count seen at registration (unless a
                    # master LR push is active — it wins)
                    self._pending_lr = linear_scale(
                        self._base_lr,
                        self._last_known_workers or self.cfg.num_workers,
                        self.cfg.num_workers,
                    )
        self._model_version = self._state.model_version

    def _maybe_checkpoint(self, force: bool = False) -> None:
        """Step-interval checkpointing (reference: --checkpoint_steps), plus
        forced saves on preemption — both taken only at task boundaries.

        Only worker 0 writes interval/preemption checkpoints: concurrent
        orbax managers over one directory race on saves and max_to_keep GC
        (the reference had the same single-writer shape — its master owned
        checkpointing). Every worker still *restores*. Master-coordinated
        SAVE_MODEL tasks (exclusive lease) may be served by any worker.
        force=True also drains any in-flight async save, so a preemption
        exit never abandons a half-written checkpoint."""
        if force and self._tier is not None:
            # the tier half of a forced save: every worker persists ITS
            # resident shards (one owner per shard — no write races),
            # seq watermarks included, so a planned kill loses no acked
            # push (the kill-worker resharding acceptance)
            try:
                self._tier.drain()
            except Exception:
                logger.exception("embedding tier drain failed")
        mngr = self._checkpoint_manager()
        if mngr is None or self._state is None or self.worker_id != 0:
            return
        if self._mid_training_task:
            # Never persist mid-task state: the task's lease is only released
            # on report, so a mid-task save + relaunch would re-apply the
            # task's records on top of updates that already include them
            # (double-counting). Saves happen only at task boundaries, where
            # state and the task queue agree exactly-once.
            if force:
                mngr.wait()
            return
        step = self._state.model_version
        due = (
            self.cfg.checkpoint_steps > 0
            and step - self._last_ckpt_step >= self.cfg.checkpoint_steps
        )
        if (force and step > self._last_ckpt_step) or due:
            mngr.save(self._state)
            self._last_ckpt_step = step
        if force:
            mngr.wait()

    # ------------------------------------------------------------------ #
    # heartbeats

    def _stats_payload(self) -> Dict[str, Any]:
        """The heartbeat telemetry payload: recent step-time quantiles +
        records/s from the rolling window, plus the control-plane state
        the master's health layer wants to see (breaker, rescale phase,
        prefetch lookahead, world generation)."""
        stats = self._step_stats.snapshot()
        if self._rescaling or self._pending_rescale is not None:
            phase = "rescale"
        elif self._mid_training_task:
            phase = "train"
        else:
            phase = "idle"
        try:
            depth = int(
                os.environ.get("EDL_PREFETCH_DEPTH", "")
                or self.cfg.prefetch_batches
            )
        except ValueError:
            depth = self.cfg.prefetch_batches
        stats.update(phase=phase, prefetch_depth=depth)
        stats.update(self._session.stats_ride_alongs(self._tier))
        return stats

    def _on_heartbeat_response(self, resp) -> None:
        """The session's heartbeat hook (heartbeat thread): what the
        master's answer means to THIS worker. Everything here only raises
        flags; the run loop acts on them at a batch or task boundary."""
        if getattr(resp, "evict", False):
            # graceful-eviction drain handshake (the closed-loop
            # autoscaler shrinking past this worker): identical to
            # a k8s SIGTERM preemption — stop at the next batch
            # boundary, drain-checkpoint, report the applied
            # prefix (the remainder requeues FRONT, retry-free),
            # exit EX_TEMPFAIL. The run loop does all of that off
            # the _preempted flag, which also ends the beats.
            logger.warning(
                "master evicted this worker (autoscale policy); "
                "draining"
            )
            tracing.event("worker.evicted", worker_id=self.worker_id)
            self.preempt()
            return
        self._last_known_workers = resp.num_workers or self._last_known_workers
        if resp.membership_version != self._membership_version:
            self._on_membership_change(
                resp.membership_version, resp.num_workers
            )
        if (
            resp.learning_rate > 0
            and resp.learning_rate != self._pushed_lr
        ):
            # master-pushed LR override (ReduceLROnPlateau): applied
            # at the next task boundary, AFTER any elastic rescale
            # set above — the push is job-global and wins
            self._pushed_lr = resp.learning_rate
            self._pending_lr = resp.learning_rate

    def _on_membership_change(self, new_version: int, num_workers: int = 0) -> None:
        """Elastic hook: the worker set changed. This worker's only local
        reaction is rescaling the LR (when scale_lr_with_workers) — its
        single-host mesh keeps running. Multi-process mesh re-formation is
        NOT done here: cohort worlds are torn down and re-formed by the
        instance manager (master/process_manager.py), with worker/cohort.py
        exiting and restoring from checkpoint."""
        logger.info(
            "membership v%d -> v%d", self._membership_version, new_version
        )
        self._membership_version = new_version
        if self._tier is not None:
            # shards may have been re-planned onto (or off) this worker;
            # the run loop executes the refresh at a task boundary
            self._tier_refresh_pending = True
        if (
            self.cfg.scale_lr_with_workers and self._base_lr and num_workers
            and not self._pushed_lr
        ):
            from elasticdl_tpu.training.lr_modulation import linear_scale

            # applied by the run loop at the next task boundary (the
            # heartbeat thread must not swap state mid-train-step). An
            # active master push (ReduceLROnPlateau) wins over the elastic
            # rescale — without this guard a membership bump would silently
            # revert the plateau reduction and the push could never re-fire
            # (resp.learning_rate == self._pushed_lr stays true)
            self._pending_lr = linear_scale(
                self._base_lr, num_workers, self.cfg.num_workers
            )

    # ------------------------------------------------------------------ #
    # in-place rescale (single-process worlds)

    def request_rescale(self, axis_sizes=None, devices=None) -> None:
        """Ask for an in-place mesh rescale, applied at the next batch/task
        boundary by the run/task loops. Single-process worlds only (the
        plain worker owns all its devices): the multi-process cohort
        re-forms through the instance manager instead — its fast path is
        the persistent compile cache + speculative neighbor compilation.
        Thread-safe in the signal-handler sense: just stores the target."""
        self._pending_rescale = (axis_sizes, devices)

    def _rescale_in_place(self, reset_services: bool = True) -> None:
        """Apply a pending rescale without the teardown/checkpoint-restore
        round trip: build the new mesh, hand the live state over
        (parallel/elastic.reshard_state moves only shards whose owner set
        changes), and swap in a Trainer that — sharing the executable
        cache and the config-derived token — reuses any speculatively
        compiled programs instead of re-tracing.

        `reset_services=False` for MID-TASK rescales: the in-flight task's
        source generator belongs to the live data service, and its batch
        shape must stay static anyway; task-boundary rescales rebuild the
        services so batch_multiple re-derives from the new data axis."""
        from elasticdl_tpu.parallel import elastic
        from elasticdl_tpu.parallel.mesh import build_mesh

        target, self._pending_rescale = self._pending_rescale, None
        if target is None:
            return
        axis_sizes, devices = target
        # heartbeat telemetry reports phase="rescale" for the duration
        # (the pending target was just consumed, so the flag is what keeps
        # the master's health view honest mid-recovery)
        self._rescaling = True
        t0 = time.perf_counter()
        # the rescale opens a NEW world generation: bump the tracer's world
        # version first so every span of this recovery carries it — rolled
        # back below if the build fails (the worker keeps running the OLD
        # world then, and telemetry must agree)
        prev_world_version = tracing.get_tracer().world_version
        tracing.set_world_version(prev_world_version + 1)
        # join the master's announced resize timeline when one exists (the
        # membership signal file carries its trace id); otherwise this
        # rescale starts its own trace
        announced_tid = membership_signal.trace_id()
        try:
            # goodput: every second of the rescale lands in the `rescale`
            # category, sub-bucketed settle/compile/handoff to mirror the
            # resize trace's phase vocabulary (the profiler's handoff
            # phase is deliberately NOT teed into the ledger — these
            # explicit adds are the one billing site)
            ledger = goodput_lib.get_ledger()
            with tracing.span(
                "rescale", trace_id=announced_tid,
                mid_task=not reset_services,
            ) as root:
                # build everything fallible FIRST, swap worker state LAST: a
                # failed construction must leave the old mesh/trainer/state
                # fully intact
                with tracing.span("rescale.mesh"), \
                        ledger.phase("rescale", sub="settle"):
                    new_mesh = build_mesh(axis_sizes, devices)
                with tracing.span("rescale.compile"), \
                        ledger.phase("rescale", sub="compile"):
                    # construction resolves the executable cache; an actual
                    # re-trace (cache miss) is deferred to the first step
                    new_trainer = self._make_trainer(new_mesh)
                new_state = self._state
                if new_state is not None:
                    with tracing.span("rescale.handoff"), \
                            ledger.phase("rescale", sub="handoff"):
                        handoff = elastic.LiveStateHandoff().capture(
                            new_state
                        )
                        new_state = handoff.apply(new_mesh)
                self._state = new_state
                self._mesh = new_mesh
                self._trainer = new_trainer
                if reset_services:
                    for svc in self._services.values():
                        svc.close()
                    self._services.clear()
                self.last_recovery_s = time.perf_counter() - t0
                root.set(
                    world_size=int(new_mesh.devices.size),
                    recovery_s=round(self.last_recovery_s, 6),
                )
        except BaseException:
            tracing.set_world_version(prev_world_version)
            raise
        finally:
            self._rescaling = False
        _RESCALES.inc()
        _RESCALE_S.observe(self.last_recovery_s)
        logger.info(
            "in-place rescale to %s in %.3fs (compile cache: %s)",
            dict(zip(new_mesh.axis_names, new_mesh.devices.shape)),
            self.last_recovery_s, self._trainer.compile_stats(),
        )

    # ------------------------------------------------------------------ #
    # task execution

    def _maybe_profile(self) -> None:
        """Drive the jax.profiler trace window (SURVEY §5 tracing): worker 0
        records steps [profile_start_step, profile_start_step+profile_steps)
        into profile_dir, skipping compile/warmup. One window per run."""
        if not self.cfg.profile_dir or self.worker_id != 0:
            return
        import jax

        if (
            self._profile_state == "idle"
            and self._global_step >= self.cfg.profile_start_step
        ):
            try:
                jax.profiler.start_trace(self.cfg.profile_dir)
                self._profile_state = "active"
                logger.info(
                    "profiler trace started at step %d -> %s",
                    self._global_step, self.cfg.profile_dir,
                )
            except Exception:
                logger.exception("profiler start failed; disabled")
                self._profile_state = "done"
        elif (
            self._profile_state == "active"
            and self._global_step
            >= self.cfg.profile_start_step + self.cfg.profile_steps
        ):
            self._stop_profiler()

    def _stop_profiler(self) -> None:
        if self._profile_state != "active":
            return
        import jax

        try:
            jax.profiler.stop_trace()
            logger.info("profiler trace stopped at step %d", self._global_step)
        except Exception:
            logger.exception("profiler stop failed")
        self._profile_state = "done"

    def _run_training_task(self, task: pb.Task) -> Dict[str, float]:
        if self.cfg.steps_per_dispatch > 1:
            return self._run_training_task_grouped(
                task, self.cfg.steps_per_dispatch)
        svc = self._data_service(pb.TRAINING)
        loss_sum, loss_count = 0.0, 0
        records_done = 0
        step_time_sum = 0.0
        interrupted = False
        self._mid_training_task = True
        # always-on step profiler (observability/profile.py): the
        # prefetcher attributes data_wait/h2d internally; this loop
        # attributes compute (the timed step region) and handoff (the
        # mid-task rescale) and closes each step's phase record
        prof = profile_lib.get_profiler()
        prefetcher = self._prefetched(
            svc.batches(task.shard_name, task.start, task.end))
        while True:
            if self._pending_rescale is not None and not self._shutdown.is_set():
                # mid-task in-place rescale: the lookahead window holds
                # device batches with the OLD mesh's shardings — drain it
                # (pending HOST batches come back), rescale, and requeue
                # the drained batches through a prefetcher on the new mesh
                # so the task's record span stays exactly-once. A failed
                # rescale (bad advisory target) must cost a log line, not
                # the task: the drained batches are requeued either way,
                # on whatever mesh the worker ends up holding.
                import itertools

                with prof.phase("handoff"):
                    leftover = prefetcher.drain()
                    source = prefetcher.source
                    try:
                        self._rescale_in_place(reset_services=False)
                    except Exception:
                        logger.exception(
                            "mid-task in-place rescale failed; mesh kept")
                    prefetcher = self._prefetched(
                        itertools.chain(iter(leftover), source))
            try:
                batch = next(prefetcher)
            except StopIteration:
                break
            if self._shutdown.is_set():
                # preemption mid-task: stop before the next batch; the drain
                # report below hands the unprocessed remainder back
                interrupted = True
                break
            self._ensure_state(batch)
            self._maybe_profile()
            # the timed region IS the compute phase: one timer feeds the
            # profiler, the histogram and the task line's ms/step
            with prof.phase("compute", steps=1) as region:
                # straggler-injection site (per-worker so a chaos schedule
                # can slow EXACTLY one worker: worker.train_step.<id>, or
                # all via the worker.train_step.* wildcard); inside the
                # timed region, so an injected delay reads as a slow step —
                # which is the point: the health layer must detect it
                faults.fire(f"worker.train_step.{self.worker_id}")
                with prof.span("compute.dispatch"):
                    self._state, logs = self._trainer.train_step(
                        self._state, batch)
                with prof.span("compute.readback"):
                    # float() forces the step's result, so this wall time
                    # covers the whole step (dispatch + device compute),
                    # not just dispatch — the sync IS the measurement:
                    # edl-lint: disable=EDL201
                    loss_sum += float(logs["loss"])
            step_s = region.seconds
            step_time_sum += step_s
            _TRAIN_STEP_S.observe(step_s)
            prof.step_done()
            loss_count += 1
            self._global_step += 1
            self._model_version += 1
            # mask sums the real (non-padding) records this batch applied;
            # exactly-once accounting needs it per batch (the drain report
            # retires records mid-task): edl-lint: disable=EDL201
            batch_records = int(batch["mask"].sum())
            records_done += batch_records
            self._step_stats.observe_step(step_s, batch_records)
        return {
            "loss_sum": loss_sum,
            "loss_count": loss_count,
            "records_done": records_done,
            "step_time_sum": step_time_sum,
            "interrupted": interrupted,
        }

    def _grouped_stream(self, stream, k, interrupted):
        """THE grouped-dispatch scaffold, shared by the training/eval/
        prediction task paths: yield lists of ready-to-run batches — full
        k-groups, then one trailing partial. Grouped mode (k > 1) buffers
        HOST batches (the wire cast is applied BEFORE _ensure_state so
        every path traces with identical feature dtypes, and the mask leaf
        is exempted by _wire_cast so record accounting stays exact);
        k == 1 yields single prefetched (device-resident, pre-cast)
        batches. On shutdown/preemption `interrupted` (a mutable list) gets
        a True appended and the stream ends at the group boundary — the
        trailing partial is NOT yielded, so drain reports cover whole
        groups only."""
        from elasticdl_tpu.data.prefetch import _wire_cast

        buf = []
        prof = profile_lib.get_profiler()
        if k == 1:
            stream = self._prefetched(stream)
        else:
            # grouped mode consumes host batches directly (no prefetcher
            # to self-time): attribute each pull to data_wait here
            stream = profile_lib.timed_iter(stream, prof)
        for batch in stream:
            if self._shutdown.is_set():
                interrupted.append(True)
                return
            if k > 1 and self.cfg.wire_dtype:
                with prof.phase("h2d"):
                    batch = _wire_cast(batch, self.cfg.wire_dtype)
            self._ensure_state(batch)
            buf.append(batch)
            if len(buf) == k:
                yield buf
                buf = []
        if buf:
            yield buf

    def _run_training_task_grouped(self, task: pb.Task, k: int) -> Dict[str, float]:
        """--steps_per_dispatch > 1: buffer k host batches, run them as ONE
        XLA dispatch (Trainer.train_many lax.scan). Exactly-once accounting
        is unchanged — a group's records count as applied only after its
        dispatch's loss is read back, and preemption stops at a group
        boundary so the drain report covers whole groups. A trailing partial
        group falls back to single train_steps (two compiled programs total,
        not one per remainder length)."""
        import jax.numpy as jnp

        from elasticdl_tpu.parallel.mesh import shard_batch_stack

        svc = self._data_service(pb.TRAINING)
        stats = {"loss_sum": 0.0, "loss_count": 0, "records_done": 0,
                 "step_time_sum": 0.0, "interrupted": False}
        self._mid_training_task = True
        interrupted: list = []

        prof = profile_lib.get_profiler()
        for buf in self._grouped_stream(
            svc.batches(task.shard_name, task.start, task.end), k, interrupted
        ):
            self._maybe_profile()
            # the timed region (the task line's ms/step): batch assembly,
            # dispatch and the readback that ends it. The assembly is billed
            # to h2d and the rest to compute; their sum is the region.
            with prof.phase("compute", steps=len(buf)) as region:
                # straggler-injection site (one per GROUP dispatch — see the
                # single-step path for the per-worker addressing rationale)
                faults.fire(f"worker.train_step.{self.worker_id}")
                if len(buf) == k:
                    with prof.phase("h2d") as put:
                        stacked = shard_batch_stack(
                            self._mesh, buf, self._spec.batch_partition)
                    region.carve(put.seconds)
                    with prof.span("compute.dispatch"):
                        self._state, m = self._trainer.train_many(
                            self._state, stacked)
                    with prof.span("compute.readback"):
                        # one sync per GROUP (k steps), deliberate — it
                        # forces the dispatch so step_time covers device
                        # compute, and grouped mode amortizes it k-fold:
                        # edl-lint: disable=EDL201
                        stats["loss_sum"] += float(jnp.sum(m["loss"]))
                else:
                    for b in buf:
                        with prof.span("compute.dispatch"):
                            self._state, logs = self._trainer.train_step(
                                self._state, b)
                        with prof.span("compute.readback"):
                            # trailing-partial fallback, same rationale as
                            # above: edl-lint: disable=EDL201
                            stats["loss_sum"] += float(logs["loss"])
            group_s = region.seconds
            stats["step_time_sum"] += group_s
            _TRAIN_STEP_S.observe(group_s / max(1, len(buf)))
            # one profile record per group, normalized per step inside
            # step_done (grouped and single-step workers stay comparable)
            prof.step_done(len(buf))
            stats["loss_count"] += len(buf)
            self._global_step += len(buf)
            self._model_version += len(buf)
            # per-group record accounting for the drain report:
            # edl-lint: disable=EDL201
            group_records = int(sum(b["mask"].sum() for b in buf))
            stats["records_done"] += group_records
            # one telemetry sample per group, normalized to per-step values
            # so grouped and single-step workers score comparably
            self._step_stats.observe_step(
                group_s / max(1, len(buf)), group_records / max(1, len(buf))
            )
        stats["interrupted"] = bool(interrupted)
        return stats

    def _report_preempted_task(self, task: pb.Task, stats: Dict[str, float]) -> None:
        """Drain protocol for an interrupted training task. Records may only
        be retired from the master's queue when a checkpoint covering them is
        durably on disk, and a drain checkpoint may only survive when its
        retirement report was accepted — otherwise either path loses or
        double-applies records:

          1. save the mid-task state (wait for durability); workers that
             don't checkpoint (worker_id != 0, no checkpoint_dir, failed
             save) report records_processed=0 → the FULL task is requeued,
             retry-free, and nothing is lost;
          2. report the applied-record count;
          3. if the master rejects the report (stale lease — e.g. the task
             timed out and was already requeued whole) or the report can't be
             delivered, delete the just-saved drain checkpoint so a relaunch
             restores the last task-boundary state instead.

        Residual window (documented at-least-once, same as the reference's
        PS mode where pushed gradients survived a task re-run): the process
        dying between (1) and (3) leaves a drain checkpoint whose task is
        re-leased in full.
        """
        mngr = self._checkpoint_manager()
        records_applied = int(stats["records_done"])
        records_done = records_applied
        drain_step = None
        if records_done > 0 and mngr is not None and self.worker_id == 0:
            try:
                drain_step = mngr.save(self._state, wait=True)
            except Exception:
                logger.exception("drain checkpoint failed; requeueing full task")
                drain_step = None
        if drain_step is None:
            records_done = 0
        delivered = False
        try:
            faults.fire("worker.report_task")
            resp = self._session.stub.ReportTaskResult(
                pb.ReportTaskResultRequest(
                    worker_id=self.worker_id,
                    task_id=task.task_id,
                    success=False,
                    preempted=True,
                    err_message="preempted",
                    records_processed=records_done,
                    loss_sum=stats["loss_sum"],
                    loss_count=int(stats["loss_count"]),
                    model_version=self._model_version,
                ),
                timeout=10,
            )
            accepted = resp.accepted
            delivered = True
        except Exception as e:
            logger.warning("preemption drain report failed to deliver: %s", e)
            accepted = False
            if is_stale_generation(e):
                # generation fence: a DEFINITIVE rejection (the fence aborts
                # before any mutation) — the restarted master replayed our
                # lease back into todo WHOLE, so the full task will re-run
                # and the drain checkpoint (covering a partial span) would
                # double-apply. Same semantics as an explicit rejection,
                # independent of whether the reconnect handshake succeeds.
                delivered = True
                self._session.maybe_reconnect(e)
        if accepted:
            # Clear the mid-task flag only when the persisted state and the
            # task queue actually agree: either the drain checkpoint covers
            # the applied records, or no records were applied at all. When
            # the save failed (full task requeued), the live state still
            # holds the requeued task's records and must NOT be persisted by
            # the post-loop forced save.
            if drain_step is not None or records_applied == 0:
                self._mid_training_task = False
            if drain_step is not None:
                self._last_ckpt_step = drain_step
        elif drain_step is not None and delivered:
            # Explicit rejection (stale lease): the full task will re-run, so
            # this checkpoint would double-apply — discard it. A DELIVERY
            # failure is ambiguous (the master may have retired the records):
            # keep the checkpoint then, since losing retired records is worse
            # than the bounded double-apply of an undelivered report
            # (at-least-once, like the reference's PS mode).
            mngr.delete(drain_step)

    def _run_evaluation_task(self, task: pb.Task) -> bool:
        """Returns True if interrupted by shutdown/preemption (no report).
        Full k-groups run as ONE eval_many scan (metric states are the
        carry — numerically equivalent to sequential steps); the scaffold
        (wire cast, buffering, prefetch selection) is _grouped_stream."""
        from elasticdl_tpu.parallel.mesh import shard_batch_stack

        svc = self._data_service(pb.EVALUATION)
        states = self._trainer.new_metric_states()
        k = max(1, self.cfg.steps_per_dispatch)
        interrupted: list = []

        for buf in self._grouped_stream(
            svc.batches(task.shard_name, task.start, task.end), k, interrupted
        ):
            if len(buf) == k and k > 1:
                states = self._trainer.eval_many(
                    self._state,
                    shard_batch_stack(
                        self._mesh, buf, self._spec.batch_partition),
                    states,
                )
            else:
                for b in buf:
                    states = self._trainer.eval_step(self._state, b, states)
        if interrupted:
            return True
        import jax

        msg = pb.ReportEvaluationMetricsRequest(
            worker_id=self.worker_id,
            eval_job_id=task.eval_job_id,
            task_id=task.task_id,
        )
        for name, state in states.items():
            arr = np.asarray(jax.device_get(state), np.float32)
            msg.states.append(pb.MetricState(name=name, data=arr.tobytes()))
        self._session.stub.ReportEvaluationMetrics(msg, timeout=30)
        return False

    def _run_prediction_task(self, task: pb.Task) -> bool:
        """Returns True if interrupted by shutdown/preemption (no report).
        Full k-groups run as one predict_many dispatch (outputs come back
        stacked, fed to the processor per batch in order); the scaffold is
        _grouped_stream."""
        import jax

        from elasticdl_tpu.parallel.mesh import shard_batch_stack
        from elasticdl_tpu.worker.prediction_outputs_processor import (
            iter_stacked,
            mask_predictions,
        )

        svc = self._data_service(pb.PREDICTION)
        processor = self._spec.prediction_outputs_processor
        k = max(1, self.cfg.steps_per_dispatch)
        interrupted: list = []

        def process(batch, outputs):
            if processor is None:
                return
            valid = np.asarray(batch["mask"]) > 0
            # pytree-safe: predict outputs may be a dict/tuple, not an array
            processor.process(
                mask_predictions(jax.device_get(outputs), valid),
                self.worker_id,
            )

        for buf in self._grouped_stream(
            svc.batches(task.shard_name, task.start, task.end), k, interrupted
        ):
            if len(buf) == k and k > 1:
                stacked = shard_batch_stack(
                    self._mesh, buf, self._spec.batch_partition)
                outs_dev = self._trainer.predict_many(self._state, stacked)
                if processor is not None:
                    # D2H only when someone consumes the outputs
                    for b, out in zip(buf, iter_stacked(outs_dev, len(buf))):
                        process(b, out)
            else:
                for b in buf:
                    process(b, self._trainer.predict_step(self._state, b))
        return bool(interrupted)

    # ------------------------------------------------------------------ #

    def _init_embedding_tier(self) -> None:
        """Join the elastic embedding tier (cfg.embedding_shards > 0):
        register this worker's owning store, build the pull/push client
        off the master's shard map, install any shards the map (or a
        checkpoint) assigns here. Best-effort at boot — a worker that
        cannot join the tier can still train dense models; models that
        NEED tier tables fail loudly at pull time instead."""
        if self.cfg.embedding_shards <= 0 or self._tier is not None:
            return
        try:
            from elasticdl_tpu.embedding.tier import WorkerTierRuntime

            transport = bind_servicer = None
            if (self.cfg.embedding_transport == "grpc"
                    and getattr(self, "_data_server", None) is not None):
                # the partition-tolerant data plane (ISSUE 15): route
                # peers' shards over gRPC through the robustness layer;
                # our own store short-circuits in-process
                from elasticdl_tpu.embedding.data_plane import (
                    GrpcTransport,
                    ResilientTransport,
                    default_policies,
                )

                budget_s = self.cfg.embedding_rpc_deadline_ms / 1e3
                queue_journal = ""
                if (self.cfg.embedding_push_queue > 0
                        and self.cfg.checkpoint_dir):
                    queue_journal = os.path.join(
                        self.cfg.checkpoint_dir,
                        f"emb-push-queue-{self.worker_id}.jsonl")
                transport = ResilientTransport(
                    GrpcTransport(default_timeout_s=budget_s,
                                  shm=self.cfg.embedding_shm),
                    policies=default_policies(budget_s),
                    staleness_bound=self.cfg.embedding_cache_staleness,
                    hedge=self.cfg.embedding_hedge_ms >= 0,
                    hedge_delay_ms=max(0, self.cfg.embedding_hedge_ms),
                    queue_journal=queue_journal,
                    queue_max=self.cfg.embedding_push_queue,
                )
                bind_servicer = self._data_server.servicer
            self._tier = WorkerTierRuntime(
                self._session.stub, self.worker_id,
                checkpoint_dir=self.cfg.checkpoint_dir,
                transport=transport,
                bind_servicer=bind_servicer,
                cache_rows=self.cfg.embedding_cache_rows,
                cache_staleness=self.cfg.embedding_cache_staleness,
                read_replicas=self.cfg.embedding_read_replicas > 0,
                pipeline_depth=self.cfg.embedding_pull_pipeline,
            )
            logger.info(
                "joined embedding tier: map v%d, %d shard(s) resident",
                self._tier.client.view.version,
                len(self._tier.store.resident_shards()),
            )
        except Exception:
            logger.exception(
                "embedding tier init failed; tier disabled for this worker"
            )

    @staticmethod
    def _startup_done(first_task: contextlib.ExitStack) -> None:
        """Close `start.first_task` and print the start-up ledger, when the
        first task's turn has ended. A second call does nothing."""
        first_task.close()
        tracing.log_startup_ledger()

    def run(self) -> int:
        with tracing.start_span("connect"):
            self._connect()
        self._init_embedding_tier()
        # /metrics + /healthz for this worker (best-effort, off the hot
        # path; a set EDL_METRICS_PORT overrides cfg.metrics_port either
        # way, -1/off in either disables)
        from elasticdl_tpu.observability.http import start_server

        self._metrics_server = start_server(
            role=f"worker-{self.worker_id}", port=self.cfg.metrics_port
        )
        # beats start BEFORE the backend comes up: reaching four chips took
        # 18 s (PR 21), and a registered worker that stays silent for three
        # beat periods is declared dead and told to leave
        self._session.start_heartbeats(
            model_version=lambda: self._model_version,
            stats_payload=self._stats_payload,
            on_response=self._on_heartbeat_response,
            fault_point="worker.heartbeat",
        )
        self._build_trainer()

        tasks_done = 0
        wait_backoff = 1.0
        prof = profile_lib.get_profiler()
        # the first lease to the end of the first task's turn: state, restore
        # and the compilations are its children, the rest (lease, input,
        # transfer, the dispatches, the report) its own time
        first_task = contextlib.ExitStack()
        first_task.enter_context(tracing.start_span("first_task"))
        # one iteration is one task turn: lease, the task, its report. Each
        # is a span of the device profiler's trace (observability/profile.py)
        # so that a gap on the device can be put down to one of them.
        while not self._shutdown.is_set():
            with prof.span("task_turn") as turn:
                if self._lease_queue:
                    # drain locally held leases before re-polling (batched
                    # leases: N tasks per GetTask round-trip)
                    task = self._lease_queue.popleft()
                else:
                    try:
                        with prof.span("lease"):
                            resp = self._session.stub.GetTask(
                                pb.GetTaskRequest(
                                    worker_id=self.worker_id,
                                    max_tasks=self.cfg.task_lease_batch,
                                ),
                                timeout=30,
                            )
                    except Exception as e:
                        logger.warning("get_task failed: %s; retrying", e)
                        if self._session.maybe_reconnect(e):
                            # master restarted: the handshake landed, re-lease
                            # immediately under the new generation
                            continue
                        if self._session.master_unreachable():
                            break
                        # jittered: a cohort of relaunched workers retrying a
                        # recovering master on the same constant beat is a
                        # thundering herd (edl-lint EDL304). Goodput: time
                        # spent riding out an unreachable master is the
                        # `reconnect` category.
                        with goodput_lib.get_ledger().phase("reconnect"):
                            time.sleep(jittered(2))
                        continue
                    if resp.job_done:
                        logger.info("job done after %d tasks", tasks_done)
                        self._session.job_done = True
                        break
                    # an old master never fills `tasks`; fall back to the
                    # classic singular field (WAIT only ever arrives alone)
                    leased = list(resp.tasks) or [resp.task]
                    task = leased[0]
                    self._lease_queue.extend(leased[1:])
                    wait_backoff = resp.backoff_seconds or 1.0
                turn.set_metadata(
                    task_id=task.task_id, type=pb.TaskType.Name(task.type))
                pending_lr, self._pending_lr = self._pending_lr, None
                if pending_lr is not None and self._state is not None:
                    from elasticdl_tpu.training.lr_modulation import (
                        apply_learning_rate,
                    )

                    self._state = apply_learning_rate(
                        self._trainer, self._state, pending_lr
                    )
                    logger.info("runtime LR set to %.6g", pending_lr)
                elif pending_lr is not None:
                    # state not built yet: keep it pending for the next loop
                    self._pending_lr = pending_lr
                if (self._session.checkpoint_requested
                        and not self._mid_training_task):
                    # master-requested checkpoint (heartbeat should_checkpoint),
                    # taken at a task boundary only
                    self._session.checkpoint_requested = False
                    try:
                        self._maybe_checkpoint(force=True)
                    except Exception:
                        logger.exception("master-requested checkpoint failed")
                if self._pending_rescale is not None:
                    # planned in-place rescale at a clean task boundary: live
                    # handoff + executable-cache reuse, no teardown (the
                    # pending target is consumed either way — no retry loop)
                    try:
                        with prof.phase("handoff"):
                            self._rescale_in_place()
                    except Exception:
                        logger.exception("in-place rescale failed; mesh kept")
                if self._tier is not None and self._tier_refresh_pending:
                    # resharding reaction at a clean task boundary: refetch
                    # the map, promote/install newly-owned shards (replica
                    # promotion first — see WorkerTierRuntime), confirm the
                    # moves, adopt new replica assignments
                    self._tier_refresh_pending = False
                    try:
                        self._tier.on_world_change()
                    except Exception:
                        logger.exception("embedding tier refresh failed")
                elif self._tier is not None:
                    # replica delta sync rides the task boundary (cheap
                    # no-op when this worker replicates nothing): replicas
                    # stay within the staleness bound of their primaries
                    # without a dedicated thread contending with the step
                    try:
                        self._tier.sync_replicas()
                    except Exception:
                        logger.exception("embedding replica sync failed")
                if task.type == pb.WAIT:
                    # jittered so an idle swarm does not re-poll in phase
                    # (epoch boundaries unblock every worker at once).
                    # Goodput: idle-with-no-task is the `lease_wait` category
                    # — the autoscaler's shrink signal.
                    with goodput_lib.get_ledger().phase("lease_wait"), \
                            prof.span("lease.wait"):
                        time.sleep(jittered(wait_backoff))
                    continue

                report = pb.ReportTaskResultRequest(
                    worker_id=self.worker_id, task_id=task.task_id, success=True
                )
                try:
                    if task.type == pb.TRAINING:
                        with prof.span("task", records=task.end - task.start):
                            stats = self._run_training_task(task)
                        _TRAIN_STEPS.inc(int(stats["loss_count"]))
                        _TRAIN_RECORDS.inc(int(stats["records_done"]))
                        if stats["step_time_sum"] > 0:
                            _TRAIN_THROUGHPUT.set(
                                stats["records_done"] / stats["step_time_sum"]
                            )
                        if stats["loss_count"]:
                            # the first task's figure includes the compile
                            logger.info(
                                "training task %d: %d step(s), %.1f ms/step, "
                                "mean loss %.4f", task.task_id,
                                stats["loss_count"],
                                1e3 * stats["step_time_sum"] / stats["loss_count"],
                                stats["loss_sum"] / stats["loss_count"],
                            )
                        if stats["interrupted"]:
                            with prof.span("report"):
                                self._report_preempted_task(task, stats)
                            break
                        report.loss_sum = stats["loss_sum"]
                        report.loss_count = int(stats["loss_count"])
                        report.step_time_sum = stats["step_time_sum"]
                        report.step_count = int(stats["loss_count"])
                    elif task.type == pb.EVALUATION:
                        if self._run_evaluation_task(task):
                            break
                    elif task.type == pb.PREDICTION:
                        if self._run_prediction_task(task):
                            break
                    elif task.type == pb.SAVE_MODEL:
                        self._save_checkpoint()
                    report.records_processed = task.end - task.start
                    if self._state is not None:
                        report.model_version = self._model_version
                except Exception as e:
                    logger.exception("task %d failed", task.task_id)
                    report.success = False
                    report.err_message = str(e)[:512]
                try:
                    with prof.span("report"):
                        faults.fire("worker.report_task")
                        self._session.stub.ReportTaskResult(report, timeout=30)
                        if task.type == pb.TRAINING and report.success:
                            # state and task queue agree here: safe
                            # checkpoint point
                            self._mid_training_task = False
                            self._maybe_checkpoint()
                except Exception as e:
                    logger.warning("report failed for task %d: %s", task.task_id, e)
                    if self._session.maybe_reconnect(e):
                        # fenced report from before the crash: the restarted
                        # master requeued this lease, so the task re-runs and
                        # retires exactly once there — never resend the report
                        # under the new generation (that WOULD double-count)
                        logger.warning(
                            "task %d report was fenced by the restarted master; "
                            "the requeued lease re-runs it", task.task_id,
                        )
                tasks_done += 1
            if tasks_done == 1:     # the first turn that ran a task to its end
                self._startup_done(first_task)

        self._startup_done(first_task)      # a job that ended before that
        # A trace window still open at exit (short job / preemption) must be
        # flushed — an unstopped trace writes nothing.
        self._stop_profiler()

        # Preemption-triggered save (reference: preemption checkpoints in
        # the checkpoint service): SIGTERM'd workers persist progress so the
        # relaunch resumes instead of retraining.
        if self._preempted:
            try:
                self._maybe_checkpoint(force=True)
            except Exception:
                logger.exception("preemption checkpoint failed")
            # the last seconds before a preemption exit are exactly what a
            # postmortem wants: cut the black box here (explicit trigger)
            flight_lib.get_recorder().dump("preempt")

        # Export runs here, not in the GetTask branch: a worker may learn the
        # job finished from the heartbeat shutdown flag (another worker took
        # the last task) without ever seeing a job_done GetTask response.
        if self._session.job_done and not self._preempted:
            self._export_final_model()

        processor = self._spec.prediction_outputs_processor if self._spec else None
        if processor is not None:
            try:
                processor.close()
            except Exception:
                logger.exception("prediction outputs processor close failed")

        # Orderly teardown; the session's close() (heartbeat thread, then
        # the channel) comes last.
        self._shutdown.set()
        if getattr(self, "_metrics_server", None) is not None:
            try:
                self._metrics_server.stop()
            except Exception:
                logger.debug("metrics endpoint stop failed", exc_info=True)
        if getattr(self, "_data_server", None) is not None:
            try:
                self._data_server.stop()
            except Exception:
                logger.debug("data-plane endpoint stop failed",
                             exc_info=True)
        # flush trace.jsonl durably (the tracer reopens on reconfigure)
        tracing.get_tracer().close()
        self._session.close()
        # A preempted worker exits non-zero (EX_TEMPFAIL) so the instance
        # manager relaunches it and recovers its lease immediately; clean
        # job-done exits return 0. A lost master is also EX_TEMPFAIL: under
        # a live manager that means relaunch; orphaned, it frees the process.
        return 75 if (self._preempted or self._session.master_lost) else 0

    def _export_final_model(self) -> None:
        """Job-end serving export (reference: model_handler → SavedModel at
        job completion). Worker 0 writes `--output`; sharded tables gather
        through device_get inside export_model."""
        if not self.cfg.output or self.worker_id != 0 or self._state is None:
            return
        try:
            from elasticdl_tpu.training.export import export_model

            export_model(
                self._state,
                self.cfg.output,
                model_def=self.cfg.model_def,
                model_params=self._spec.model_params,
                module_name=self._spec.module_name,
            )
        except Exception:
            logger.exception("final model export failed")

    def preempt(self) -> None:
        """SIGTERM hook: finish/abandon the current batch, checkpoint, exit."""
        logger.info("preemption signal received; draining")
        self._preempted = True
        self._shutdown.set()

    def _save_checkpoint(self) -> None:
        """Serve a SAVE_MODEL task: persist current state, wait for
        durability. With no live state (a relaunched worker that has not
        processed a batch yet), success is only reported if a checkpoint
        already exists on disk — that checkpoint IS the current state, since
        no training happened since restore. Otherwise fail the task so the
        dispatcher retries it on a worker that has state (silent success
        here would retire the job's durability task with nothing saved)."""
        mngr = self._checkpoint_manager()
        if mngr is None:
            # A SAVE_MODEL task with no checkpoint_dir cannot persist
            # anything; silent success would retire the job's durability
            # task with nothing saved. Fail loudly — the dispatcher's
            # bounded retries (max_task_retries) then fail it permanently.
            raise RuntimeError(
                "SAVE_MODEL: no checkpoint_dir configured, nothing to save to"
            )
        if self._state is None:
            if mngr.latest_step(refresh=True) is None:
                raise RuntimeError(
                    "SAVE_MODEL: no live training state and no checkpoint on "
                    "disk to vouch for"
                )
            return
        mngr.save(self._state, wait=True)
        self._last_ckpt_step = self._state.model_version
