"""Multi-process SPMD worker cohort: one logical worker, one global mesh.

Reference parity: the reference's elastic-AllReduce mode (SURVEY §3.4) — N
worker pods formed one Horovod ring, each trained on its own minibatches,
gradients averaged collectively. Rebuilt TPU-native: N processes initialize
ONE `jax.distributed` world and ONE mesh over all their devices; every
process executes the same jitted train step (SPMD), each feeding its
process-local rows of the global batch; gradient averaging is the `psum`
XLA inserts over the `data` axis.

Topology of control: process 0 (the leader) is the only one the master
sees — it leases tasks, reports results, and heartbeats. Followers receive
a small broadcast control vector per task (op, shard, span, flags) and run
the identical data/compute sequence. Every collective (train step, eval,
checkpoint save/restore, export gather) is executed by ALL processes; all
host-side decisions ride the control broadcast, so the cohort stays in
lockstep by construction.

Elasticity = cohort re-formation (SURVEY §7 hard-part 1): any member dying
makes the coordination service fail the others; the whole cohort exits and
the process manager relaunches it; the new world restores from the latest
checkpoint and re-leases at the task boundary.

SIGTERM (planned preemption): a FOLLOWER exits immediately (EX_TEMPFAIL) —
it cannot drain, because the leader would keep broadcasting control vectors
it no longer answers. The LEADER, however, drains collectively: it finishes
the in-flight task, then broadcasts OP_ABORT|FLAG_CHECKPOINT so every
process joins one final collective save before exiting EX_TEMPFAIL — the
relaunched cohort restores at the pre-kill step, so a planned preemption
redoes at most the records of one partially-reported task instead of
`steps_per_dispatch x checkpoint_steps` worth of work (see
`request_preempt`).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from elasticdl_tpu.common import membership_signal
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.common.constants import ExitCode
from elasticdl_tpu.common.log_utils import default_logger
from elasticdl_tpu.data.reader import create_data_reader
from elasticdl_tpu.observability import flight as flight_lib
from elasticdl_tpu.observability import goodput as goodput_lib
from elasticdl_tpu.observability import profile as profile_lib
from elasticdl_tpu.observability.health import WorkerStepStats, encode_stats
from elasticdl_tpu.parallel.elastic import (
    CohortContext,
    context_from_env,
    make_global_batch,
    make_global_batch_stack,
)
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.proto.service import jittered
from elasticdl_tpu.training.model_spec import ModelSpec
from elasticdl_tpu.worker.session import MasterSession, job_checkpoint_manager
from elasticdl_tpu.worker.task_data_service import TaskDataService

logger = default_logger(__name__)

# control vector:
#   [op, task_id, task_type, shard_idx, start, end, flags, eval_job, lr_bits]
# lr_bits = float64 bit-pattern of the master-pushed LR override (0 = none);
# riding the broadcast keeps every process applying the same LR at the same
# task boundary (SPMD lockstep).
OP_NOOP, OP_TASK, OP_DONE, OP_ABORT = 0, 1, 2, 3
FLAG_CHECKPOINT = 1
CTRL_LEN = 9


def _lr_to_bits(lr: float) -> int:
    return 0 if not lr else int(np.float64(lr).view(np.int64))


def _bits_to_lr(bits: int) -> float:
    return 0.0 if not bits else float(np.int64(bits).view(np.float64))



class CohortWorker:
    def __init__(self, cfg: JobConfig, ctx: Optional[CohortContext] = None):
        self.cfg = cfg
        self.ctx = ctx or context_from_env(cfg)
        self._trainer = None
        self._state = None
        self._spec: Optional[ModelSpec] = None
        self._mesh = None
        self._services: Dict[int, TaskDataService] = {}
        self._shards: Dict[int, List[Tuple[str, int, int]]] = {}
        self._ckpt_manager = None
        self._last_ckpt_step = 0
        self._shutdown = threading.Event()
        # leader only (followers never talk to the master): the channel,
        # the stub, the cohort's worker id and the liveness protocol. A
        # lost master flips the shutdown that turns the next control
        # vector into OP_ABORT, taking the WHOLE cohort down EX_TEMPFAIL.
        self._session = MasterSession(
            cfg, self._shutdown, what="cohort leader",
            when_lost="aborting cohort (EX_TEMPFAIL)",
            on_reregistered=self._on_reregistered,
        )
        self._preempt = False         # leader: SIGTERM drain requested
        # Plain-int mirror of state.model_version for the heartbeat thread:
        # int(state.step) blocks on the in-flight donated computation (see
        # worker.py's identically-named field), which would stall heartbeats
        # for the length of a dispatch.
        self._model_version = 0
        self._pushed_lr = 0.0         # leader: last LR override from heartbeat
        self._ctrl_pushed_lr = 0.0    # all: latest override from the ctrl vector
        self._applied_push_lr = 0.0   # all: last override applied to state
        # rescale fast path: first trained host batch (the speculative
        # compiler's example input) + the background compiler itself
        self._example_host_batch = None
        self._spec_compiler = None
        # cohort-aggregated membership: master-assigned ids for this
        # cohort's member processes 1..N-1 (leader only; empty for
        # single-process worlds). Their beats ride the leader's single
        # Heartbeat as MemberBeat entries.
        self._member_ids: List[int] = []
        # batched leases (--task_lease_batch): leases still to broadcast,
        # drained before the next GetTask poll; cleared on reconnect
        self._lease_queue: "deque" = deque()
        # heartbeat telemetry (observability/health.py): every process —
        # leader AND followers — keeps its own step-stats window now
        # (followers force their local view of each collective dispatch),
        # exchanged to the leader over the cohort's collective channel
        # (allgather_ints) so MemberBeats carry REAL follower step times
        self._step_stats = WorkerStepStats()
        self._phase = "boot"          # boot -> train/idle (leader payload)
        # leader: latest follower-local stats rows by process index
        # (written by the task loop at the post-task exchange, read by the
        # heartbeat thread — whole-dict swaps only, so no lock needed)
        self._member_stats: Dict[int, Dict[str, Any]] = {}
        # elastic embedding tier (cfg.embedding_shards > 0): leader-owned
        # store + client; shards drain to checkpoint at teardown and the
        # next generation's leader restores them (_init_embedding_tier)
        self._tier = None

    # ------------------------------------------------------------------ #
    # setup (identical on every process)

    def _build(self) -> None:
        import jax

        from elasticdl_tpu.common.runtime import (
            configure_jax_runtime,
            log_training_devices,
        )
        from elasticdl_tpu.parallel.mesh import build_job_mesh
        from elasticdl_tpu.training.trainer import Trainer

        configure_jax_runtime(self.cfg)
        self._spec = ModelSpec.from_config(self.cfg)
        self._mesh = build_job_mesh(self.cfg, jax.devices())
        log_training_devices(self._mesh)
        from elasticdl_tpu.training import compile_cache as cc

        # config-derived token: a re-formed generation at the same mesh
        # shape (and the speculative compiler's neighbor trainers) share
        # executables instead of re-tracing
        self._trainer = Trainer(
            self._spec, self._mesh, remat=self.cfg.remat, remat_policy=self.cfg.remat_policy,
            grad_accum=self.cfg.grad_accum_steps,
            seed=self.cfg.shuffle_seed,
            cache_token=cc.job_cache_token(self.cfg),
        )

    def _data_service(self, task_type: int) -> TaskDataService:
        if task_type not in self._services:
            paths = {
                pb.TRAINING: self.cfg.training_data,
                pb.EVALUATION: self.cfg.validation_data or self.cfg.training_data,
                pb.PREDICTION: self.cfg.prediction_data,
            }
            mode = {
                pb.TRAINING: "training",
                pb.EVALUATION: "evaluation",
                pb.PREDICTION: "prediction",
            }[task_type]
            reader = create_data_reader(
                paths[task_type], self.cfg.data_reader,
                **self.cfg.data_reader_params,
            )
            parse = self._spec.dataset_fn(mode, reader.metadata)
            from elasticdl_tpu.parallel.mesh import data_axis

            multiple = dict(
                zip(self._mesh.axis_names, self._mesh.devices.shape)
            )[data_axis(self._mesh)]
            self._services[task_type] = TaskDataService(
                reader, parse, self.cfg.minibatch_size, batch_multiple=multiple
            )
            # shard index -> name map; identical everywhere (sorted) so a
            # broadcast int addresses the same shard on every process
            self._shards[task_type] = sorted(reader.create_shards())
        return self._services[task_type]

    def _shard_name(self, task_type: int, shard_idx: int) -> str:
        self._data_service(task_type)
        return self._shards[task_type][shard_idx][0]

    def _shard_index(self, task_type: int, name: str) -> int:
        self._data_service(task_type)
        for i, (n, _, _) in enumerate(self._shards[task_type]):
            if n == name:
                return i
        raise KeyError(f"unknown shard {name!r}")

    def _checkpoint_manager(self):
        if self._ckpt_manager is None:
            self._ckpt_manager = job_checkpoint_manager(self.cfg)
        return self._ckpt_manager

    def _ensure_state(self, example_batch) -> None:
        if self._state is not None:
            return
        self._state = self._trainer.init_state(example_batch)
        mngr = self._checkpoint_manager()
        if mngr is not None and mngr.latest_step() is not None:
            restored = mngr.restore(self._state)
            if restored is not None:
                self._state = restored
                self._last_ckpt_step = self._state.model_version
                logger.info(
                    "cohort resumed from checkpoint at step %d",
                    self._last_ckpt_step,
                )
        self._model_version = self._state.model_version
        if self.ctx.num_processes != self.cfg.num_processes:
            # Dynamic resizing does NOT change the effective global batch in
            # cohort mode: every generation consumes the same
            # cfg.minibatch_size rows per step (make_global_batch hands each
            # device a slice of one identical host batch), so the linear
            # LR-scaling rule does not apply — only per-device slice size
            # changed. This differs from independent (non-cohort) workers,
            # where worker count multiplies the global batch and
            # worker.py DOES rescale via lr_modulation.linear_scale.
            logger.info(
                "cohort world resized %d -> %d processes; global batch and "
                "LR unchanged (strong scaling)",
                self.cfg.num_processes, self.ctx.num_processes,
            )

    # ------------------------------------------------------------------ #
    # leader-only: master RPCs

    @property
    def worker_id(self) -> int:
        return self._session.worker_id

    def _connect(self) -> None:
        import socket

        name = f"cohort-{socket.gethostname()}:{os.getpid()}"
        # the leader is always worker 0, so boot-registration retries carry
        # the REREGISTER marker and a successor master treats them as an
        # idempotent reconnect of the journaled member, not a ghost second
        # join
        resp = self._session.connect(
            name, 0,
            # cohort-aggregated membership: member processes join in the
            # SAME round-trip as telemetry entities — the master's fleet
            # view is per-process while reap/version stay per-cohort
            member_names=self._member_names(name),
        )
        self._member_ids = list(resp.member_ids)
        logger.info(
            "cohort leader registered as worker %d (%d processes, %d devices"
            ", %d member entries)",
            self.worker_id, self.ctx.num_processes,
            len(__import__("jax").devices()), len(self._member_ids),
        )

    def _member_names(self, leader_name: str) -> List[str]:
        """Stable per-process member identities (processes 1..N-1; the
        leader itself IS the cohort's logical worker entry). The session
        re-sends them with every reconnect handshake, so a restarted
        master's register_members is idempotent."""
        return [
            f"{leader_name}#p{i}" for i in range(1, self.ctx.num_processes)
        ]

    def _init_embedding_tier(self) -> None:
        """Leader-only tier membership (cfg.embedding_shards > 0): the
        cohort is ONE logical worker, so the leader owns its shard set.
        Unlike the single-process worker there is no in-place refresh
        path — a cohort rides every world change through teardown +
        re-form (process_manager), and each generation's leader re-joins
        here, restoring its shards from the drain checkpoint."""
        if self.cfg.embedding_shards <= 0 or self._tier is not None:
            return
        try:
            from elasticdl_tpu.embedding.tier import WorkerTierRuntime

            self._tier = WorkerTierRuntime(
                self._session.stub, self.worker_id,
                checkpoint_dir=self.cfg.checkpoint_dir,
                cache_rows=self.cfg.embedding_cache_rows,
                cache_staleness=self.cfg.embedding_cache_staleness,
                read_replicas=self.cfg.embedding_read_replicas > 0,
                pipeline_depth=self.cfg.embedding_pull_pipeline,
            )
            logger.info(
                "cohort leader joined embedding tier: map v%d, %d "
                "shard(s) resident", self._tier.client.view.version,
                len(self._tier.store.resident_shards()),
            )
        except Exception:
            logger.exception(
                "embedding tier init failed; tier disabled for this cohort"
            )

    def _drain_embedding_tier(self) -> None:
        """The tier half of the cohort's drain: persist resident shards
        (rows + exactly-once watermarks) so the next generation's leader
        restores them bit-exactly."""
        if self._tier is None:
            return
        try:
            self._tier.drain()
        except Exception:
            logger.exception("embedding tier drain failed")

    def _on_reregistered(self, resp) -> None:
        """A reconnect handshake landed (worker/session.py). The cohort
        itself keeps running throughout — only the leader's control-plane
        session is re-established; followers never notice."""
        # the master's replay requeued every lease whole — drop the local
        # queue; fresh leases re-run the tasks exactly once
        self._lease_queue.clear()
        self._member_ids = list(resp.member_ids)
        logger.warning(
            "cohort leader re-registered with restarted master as worker %d; "
            "resuming leases under the new generation", self.worker_id,
        )

    def _stats_payload(self):
        """Leader heartbeat telemetry (the cohort's collective cadence as
        seen from the leader's dispatch clock; follower profiles ride
        their MemberBeats via the exchange)."""
        stats = self._step_stats.snapshot()
        stats.update(
            phase=self._phase, num_processes=self.ctx.num_processes)
        stats.update(self._session.stats_ride_alongs(self._tier))
        return stats

    def _member_beats(self) -> List[pb.MemberBeat]:
        """Coalesced per-member beats riding the leader's ONE heartbeat
        (cohort-aggregated membership). Each member entry carries that
        FOLLOWER's OWN step telemetry when the post-task collective
        exchange (`_exchange_member_stats`, over the cohort's existing
        broadcast/allgather channel) has delivered a row — real follower
        step times, per-host data-wait/h2d/compute attribution included —
        and falls back to the leader's collective cadence for a follower
        no exchange has covered yet (a just-reformed world). Fleet-scale
        telemetry still costs O(cohorts) RPCs; only the in-cohort channel
        moved, and it rides collectives the task boundary already pays."""
        if not self._member_ids:
            return []
        base = self._step_stats.snapshot()
        member_stats = self._member_stats   # whole-dict snapshot (atomic)
        beats = []
        for idx, mid in enumerate(self._member_ids, start=1):
            row = member_stats.get(idx)
            if row is not None:
                stats = dict(row)
                stats["source"] = "follower-local"
            else:
                stats = dict(base)
                stats["source"] = "leader-coalesced"
            stats.update(phase=self._phase, process_index=idx)
            beats.append(pb.MemberBeat(
                worker_id=mid,
                model_version=self._model_version,
                stats_json=encode_stats(stats),
            ))
        return beats

    #: fields of the fixed-width int64 exchange row, in wire order (times
    #: in microseconds, rates in milli-units — integers survive the int64
    #: channel exactly; floats would need a bit-pattern dance)
    _EXCHANGE_FIELDS = (
        "steps", "step_p50_us", "step_p90_us", "step_max_us",
        "records_per_s_milli", "phase_data_wait_us", "phase_h2d_us",
        "phase_compute_us",
    )

    def _exchange_row(self) -> List[int]:
        """This process's stats as the fixed-width integer row."""
        snap = self._step_stats.snapshot()
        prof = profile_lib.get_profiler().snapshot(update_memory=False)
        return [
            int(snap.get("steps", 0)),
            int(1e3 * snap.get("step_p50_ms", 0.0)),
            int(1e3 * snap.get("step_p90_ms", 0.0)),
            int(1e3 * snap.get("step_max_ms", 0.0)),
            int(1e3 * snap.get("records_per_s", 0.0)),
            int(1e3 * prof.get("phase_data_wait_ms", 0.0)),
            int(1e3 * prof.get("phase_h2d_ms", 0.0)),
            int(1e3 * prof.get("phase_compute_ms", 0.0)),
        ]

    @classmethod
    def _decode_exchange_row(cls, row) -> Dict[str, Any]:
        """Back to the heartbeat-payload schema (ms / records-per-s)."""
        vals = dict(zip(cls._EXCHANGE_FIELDS, (int(v) for v in row)))
        out: Dict[str, Any] = {"steps": vals["steps"]}
        if vals["steps"]:
            out.update(
                step_p50_ms=round(vals["step_p50_us"] / 1e3, 3),
                step_p90_ms=round(vals["step_p90_us"] / 1e3, 3),
                step_max_ms=round(vals["step_max_us"] / 1e3, 3),
                records_per_s=round(vals["records_per_s_milli"] / 1e3, 3),
            )
        for us_key, ms_key in (
            ("phase_data_wait_us", "phase_data_wait_ms"),
            ("phase_h2d_us", "phase_h2d_ms"),
            ("phase_compute_us", "phase_compute_ms"),
        ):
            if vals[us_key]:
                out[ms_key] = round(vals[us_key] / 1e3, 3)
        return out

    def _exchange_member_stats(self) -> None:
        """COLLECTIVE: every process contributes its local stats row via
        the cohort's allgather channel (parallel/elastic.py — the same
        int32-halved int64 wire the control broadcast rides); the leader
        keeps the follower rows for the next heartbeat's MemberBeats.

        Called at the end of every TRAINING task body, a point all
        processes reach in lockstep (the task_type gate branches
        identically everywhere — the control vector is shared state).
        Closes PR 7's "follower->leader channel" future-work note. A
        failed collective degrades the members to leader-coalesced
        telemetry, never the task."""
        if self.ctx.num_processes <= 1:
            return
        try:
            rows = self.ctx.allgather_ints(self._exchange_row())
        except Exception:
            logger.warning(
                "member-stats allgather failed; member beats fall back to "
                "leader-coalesced", exc_info=True,
            )
            return
        if not self.ctx.is_leader:
            return
        fresh: Dict[int, Dict[str, Any]] = {}
        for idx in range(1, min(len(rows), self.ctx.num_processes)):
            fresh[idx] = self._decode_exchange_row(rows[idx])
        self._member_stats = fresh   # atomic swap; heartbeat thread reads

    def _heartbeat_fields(self) -> Dict[str, Any]:
        try:
            return {"members": self._member_beats()}
        except Exception:
            return {}               # member telemetry never costs the beat

    def _on_heartbeat_response(self, resp) -> None:
        if resp.learning_rate > 0:
            # rides the next control vector (lr_bits) so every
            # process applies it at the same task boundary
            self._pushed_lr = resp.learning_rate

    def request_preempt(self) -> bool:
        """Leader SIGTERM hook (signal-handler safe: sets a flag, no I/O).
        Returns True when this process can drain the cohort — the next
        control vector becomes OP_ABORT|FLAG_CHECKPOINT, a COLLECTIVE save
        every process joins before exiting EX_TEMPFAIL. Returns False on
        followers (caller should exit immediately; see module docstring).
        The in-flight task completes first, so the drain window is bounded
        by one task — within k8s's default 30 s grace for the task sizes
        the dispatcher hands out, and a lost race just degrades to the
        old relaunch-and-restore path."""
        if not self.ctx.is_leader:
            return False
        self._preempt = True
        return True

    def _lease_control(self) -> List[int]:
        """Leader: turn the next master response into a control vector."""
        if self._preempt and not self._shutdown.is_set():
            logger.info("leader preempted: draining cohort via collective "
                        "checkpoint")
            ctrl = [OP_ABORT] + [0] * (CTRL_LEN - 1)
            ctrl[6] = FLAG_CHECKPOINT
            return ctrl
        if self._shutdown.is_set():
            ctrl = [OP_DONE if self._session.job_done else OP_ABORT] + [0] * (CTRL_LEN - 1)
            if self._session.master_lost:
                # the heartbeat thread crossed the unreachable limit while a
                # task was running: same final-collective-save semantics as
                # the GetTask-path abort below (the save needs no master)
                ctrl[6] = FLAG_CHECKPOINT
            return ctrl
        if self._lease_queue:
            # drain locally held leases (batched GetTask) before re-polling
            task = self._lease_queue.popleft()
        else:
            try:
                with profile_lib.get_profiler().span("lease"):
                    resp = self._session.stub.GetTask(
                        pb.GetTaskRequest(
                            worker_id=self.worker_id,
                            max_tasks=self.cfg.task_lease_batch,
                        ),
                        timeout=30,
                    )
            except Exception as e:
                logger.warning("cohort get_task failed: %s", e)
                if self._session.maybe_reconnect(e):
                    # master restarted; handshake landed — the cohort stays
                    # up and the next control vector re-leases under the
                    # new generation
                    return [OP_NOOP] + [0] * (CTRL_LEN - 1)
                if self._session.master_unreachable():
                    # carry FLAG_CHECKPOINT: we sit at a clean task boundary
                    # and the collective save needs no master, so a
                    # partitioned-but-relaunched cohort resumes here instead
                    # of redoing up to checkpoint_steps of work (same path
                    # as the SIGTERM drain)
                    ctrl = [OP_ABORT] + [0] * (CTRL_LEN - 1)
                    ctrl[6] = FLAG_CHECKPOINT
                    return ctrl
                return [OP_NOOP] + [0] * (CTRL_LEN - 1)
            if resp.job_done:
                self._session.job_done = True
                return [OP_DONE] + [0] * (CTRL_LEN - 1)
            # old master: `tasks` empty, fall back to the singular field
            leased = list(resp.tasks) or [resp.task]
            task = leased[0]
            self._lease_queue.extend(leased[1:])
        if task.type == pb.WAIT:
            return [OP_NOOP] + [0] * (CTRL_LEN - 1)
        due = (
            self.cfg.checkpoint_steps > 0
            and self._state is not None
            and self._state.model_version - self._last_ckpt_step
            >= self.cfg.checkpoint_steps
        )
        if self._session.checkpoint_requested:
            # clear only when consumed: an unconditional clear could drop a
            # request the heartbeat thread set between read and clear, and
            # the servicer's should_checkpoint bit is one-shot
            self._session.checkpoint_requested = False
            due = True
        return [
            OP_TASK, task.task_id, task.type,
            (
                0 if task.type == pb.SAVE_MODEL
                else self._shard_index(task.type, task.shard_name)
            ),
            task.start, task.end,
            FLAG_CHECKPOINT if due else 0,
            task.eval_job_id,
            _lr_to_bits(self._pushed_lr),
        ]

    # ------------------------------------------------------------------ #
    # rescale fast path: speculative neighbor-world compilation

    def _maybe_start_speculative_compiler(self) -> None:
        """Steady state reached (first training batches ran): start the
        background precompiler for neighbor world sizes — N±1 plus any size
        the master's pending-membership signal announces — so the reform,
        when it lands, finds its executables already in the in-memory cache
        (same process: in-place/test worlds) or the persistent on-disk
        cache (re-formed processes). Opt-in via --speculative_compile;
        everything here is best-effort and must never take training down.

        Scale-up caveat: a larger world's devices may not be visible from
        this process (real multi-host TPU) — those sizes are skipped, and
        the persistent cache populated by the first post-reform process
        is the warmth mechanism instead."""
        if (
            self._spec_compiler is not None
            or not self.cfg.speculative_compile
            or self._example_host_batch is None
            or self._state is None
        ):
            return
        import jax

        from elasticdl_tpu.training import compile_cache as cc

        local = max(1, len(jax.local_devices()))
        total = len(jax.devices())
        example = self._example_host_batch
        k = max(1, self.cfg.steps_per_dispatch)
        cfg, spec = self.cfg, self._spec

        def compile_for_size(size: int) -> None:
            need = size * local
            if need < 1 or need > total:
                raise cc.SpeculativeCompiler.SkipSize(
                    f"world size {size} needs {need} devices, "
                    f"{total} visible"
                )
            from elasticdl_tpu.parallel.mesh import build_job_mesh
            from elasticdl_tpu.training.trainer import Trainer

            mesh = build_job_mesh(cfg, jax.devices()[:need])
            trainer = Trainer(
                spec, mesh, remat=cfg.remat, remat_policy=cfg.remat_policy,
                grad_accum=cfg.grad_accum_steps, seed=cfg.shuffle_seed,
                cache_token=cc.job_cache_token(cfg),
            )
            # execution-free: lower+compile against abstract state/batch —
            # never runs anything on the neighbor mesh (whose peers, in a
            # real multi-process world, would not be there to collectivize)
            abs_state = trainer.abstract_train_state(example)
            trainer.aot_compile_train_step(
                abs_state, example, speculative=True, abstract=True)
            if k > 1:
                from elasticdl_tpu.parallel.mesh import abstract_batch_stack

                trainer.aot_compile_train_many(
                    abs_state,
                    abstract_batch_stack(mesh, example, k,
                                         spec.batch_partition),
                    speculative=True,
                )

        self._spec_compiler = cc.SpeculativeCompiler(
            compile_for_size,
            self.ctx.num_processes,
            signal_path=os.environ.get(membership_signal.ENV_VAR, ""),
            poll_s=max(1.0, self.cfg.worker_heartbeat_s / 2),
        )
        self._spec_compiler.start()
        logger.info(
            "speculative compiler started (world size %d, candidates %s)",
            self.ctx.num_processes, self._spec_compiler.candidate_sizes(),
        )

    # ------------------------------------------------------------------ #
    # collective task execution (every process)

    def _process_predictions(self, outputs, host_batch) -> None:
        """Collective: allgather the sharded prediction outputs so the
        leader holds the full batch, then run the user's processor there
        (reference parity: BasePredictionOutputsProcessor.process(outputs,
        worker_id) per worker — the cohort IS one logical worker, so its
        predictions flow through one processor on the leader)."""
        processor = self._spec.prediction_outputs_processor
        if processor is None:
            return
        import jax

        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            # collective — every process participates, leader consumes
            full = multihost_utils.process_allgather(outputs)
        else:
            full = jax.device_get(outputs)
        if not self.ctx.is_leader:
            return
        from elasticdl_tpu.worker.prediction_outputs_processor import (
            mask_predictions,
        )

        valid = np.asarray(host_batch["mask"]) > 0
        # pytree-safe: predict outputs may be a dict/tuple, not an array
        processor.process(mask_predictions(full, valid), self.worker_id)

    def _maybe_apply_ctrl_lr(self) -> None:
        """Apply the latest ctrl-carried LR override once state exists.
        Called at the task boundary AND after _ensure_state: a relaunched
        cohort builds state lazily from a pre-push checkpoint (stale LR in
        its opt_state), and must not run its whole first task on it. Every
        process reaches the same call sites with the same ctrl value, so
        lockstep holds; a non-modulated optimizer logs instead of crashing
        (deterministically on all processes)."""
        pushed_lr = self._ctrl_pushed_lr
        if pushed_lr > 0 and pushed_lr != self._applied_push_lr and \
                self._state is not None:
            from elasticdl_tpu.training.lr_modulation import (
                apply_learning_rate,
            )

            self._state = apply_learning_rate(
                self._trainer, self._state, pushed_lr)
            self._applied_push_lr = pushed_lr
            logger.info("applied master-pushed LR %g", pushed_lr)

    def _run_task(self, ctrl: List[int]) -> None:
        import jax

        self._phase = "train"
        try:
            self._run_task_inner(ctrl, jax)
        finally:
            self._phase = "idle"

    def _run_task_inner(self, ctrl: List[int], jax) -> None:
        _, task_id, task_type, shard_idx, start, end, flags, eval_job, lr_bits = ctrl
        self._ctrl_pushed_lr = _bits_to_lr(lr_bits)
        self._maybe_apply_ctrl_lr()
        if task_type == pb.SAVE_MODEL:
            # The master's final exclusive save task: a collective checkpoint
            # (every process writes its addressable shards), leader reports.
            # With no live state (relaunched cohort, no batch processed yet)
            # success is only true if a checkpoint already exists on disk —
            # it IS the current state then; otherwise report failure so the
            # dispatcher retries (all processes branch identically: state
            # and the checkpoint dir are symmetric across the cohort).
            mngr = self._checkpoint_manager()
            ok, err = True, ""
            if mngr is None:
                # No checkpoint_dir: nothing can be persisted. Reporting
                # success would retire the job's durability task with
                # nothing saved — fail it so the dispatcher's bounded
                # retries surface the misconfiguration (all processes
                # branch identically: the config is cohort-symmetric).
                ok, err = False, "no checkpoint_dir configured, nothing to save to"
            elif self._state is not None:
                mngr.save(self._state, wait=True)
                self._last_ckpt_step = self._state.model_version
            elif mngr.latest_step(refresh=True) is None:
                ok, err = False, "no live state and no checkpoint on disk"
            if self.ctx.is_leader:
                try:
                    self._session.stub.ReportTaskResult(
                        pb.ReportTaskResultRequest(
                            worker_id=self.worker_id, task_id=task_id,
                            success=ok, err_message=err,
                            model_version=(
                                self._state.model_version
                                if self._state is not None else 0
                            ),
                        ),
                        timeout=30,
                    )
                except Exception as e:
                    logger.warning(
                        "cohort report failed for save task %d: %s", task_id, e
                    )
                    self._session.maybe_reconnect(e)
            return
        svc = self._data_service(task_type)
        shard = self._shard_name(task_type, shard_idx)
        loss_sum, loss_count = 0.0, 0
        step_time_sum = 0.0
        metric_states = None
        k = max(1, self.cfg.steps_per_dispatch)
        buf: List[Any] = []   # host batches awaiting one grouped dispatch
        prof = profile_lib.get_profiler()

        def flush_training_group():
            """Run the buffered host batches: one train_many dispatch for a
            full k-group (every process dispatches the identical program —
            collective), single steps for a trailing partial (so only two
            compiled programs exist, not one per remainder length).

            EVERY process forces its local view of the dispatch (the
            leader via float(loss), followers via block_until_ready) so
            follower step times are REAL wall times — the train step is a
            lockstep collective, so the follower sync completes with the
            leader's and costs no extra device time; what it buys is each
            process's own host-side/data-path skew showing up in ITS
            telemetry (the member-stats exchange ships it to the leader).
            """
            nonlocal loss_sum, loss_count, step_time_sum
            if not buf:
                return
            import jax
            import jax.numpy as jnp

            # batch assembly stays OUTSIDE the timed region — step_time_ms
            # has always meant dispatch + device compute, and host-side
            # stack/H2D would otherwise read as a phantom slowdown (the
            # profiler books it under h2d instead)
            if len(buf) == k and k > 1:
                with prof.phase("h2d"):
                    stacked = make_global_batch_stack(
                        self._mesh, buf, self._spec.batch_partition
                    )
                with prof.phase("compute", steps=len(buf)) as region:
                    with prof.span("compute.dispatch"):
                        self._state, m = self._trainer.train_many(
                            self._state, stacked)
                    with prof.span("compute.readback"):
                        if self.ctx.is_leader:
                            loss_sum += float(jnp.sum(m["loss"]))
                        else:
                            # follower-local completion barrier (see
                            # docstring): edl-lint: disable=EDL201
                            jax.block_until_ready(m["loss"])
            else:
                with prof.phase("h2d"):
                    globals_ = [
                        make_global_batch(
                            self._mesh, b, self._spec.batch_partition)
                        for b in buf
                    ]
                with prof.phase("compute", steps=len(buf)) as region:
                    for gb in globals_:
                        with prof.span("compute.dispatch"):
                            self._state, logs = self._trainer.train_step(
                                self._state, gb)
                        with prof.span("compute.readback"):
                            if self.ctx.is_leader:
                                # deliberate sync: forces the collective
                                # dispatch so step_time is honest (see
                                # comment below): edl-lint: disable=EDL201
                                loss_sum += float(logs["loss"])
                            else:
                                # follower twin of the leader's float():
                                # edl-lint: disable=EDL201
                                jax.block_until_ready(logs["loss"])
            # wall time covers dispatch + device compute on THIS process
            # (every process forced its own view above)
            group_s = region.seconds
            if self.ctx.is_leader:
                step_time_sum += group_s
                loss_count += len(buf)
            # per-step telemetry sample for the heartbeat payload / the
            # member-stats exchange (the whole cohort advances
            # minibatch_size rows per step)
            self._step_stats.observe_step(
                group_s / max(1, len(buf)), self.cfg.minibatch_size
            )
            prof.step_done(len(buf))
            self._model_version += len(buf)
            buf.clear()

        pred_buf: List[Any] = []

        def flush_predict_group():
            """Prediction twin: a full k-group is ONE collective
            predict_many dispatch; each batch's (sharded) output slice then
            allgathers through _process_predictions in order. Trailing
            partials run as single collective predict_steps."""
            if not pred_buf:
                return
            if len(pred_buf) == k and k > 1:
                outs = self._trainer.predict_many(
                    self._state,
                    make_global_batch_stack(
                        self._mesh, pred_buf, self._spec.batch_partition),
                )
                for i, hb in enumerate(pred_buf):
                    # tree-indexed: outs leaves carry the group dim, and
                    # predict outputs may be a dict/tuple pytree
                    self._process_predictions(
                        jax.tree_util.tree_map(lambda x, i=i: x[i], outs), hb
                    )
            else:
                for hb in pred_buf:
                    gb = make_global_batch(
                        self._mesh, hb, self._spec.batch_partition)
                    self._process_predictions(
                        self._trainer.predict_step(self._state, gb), hb)
            pred_buf.clear()

        eval_buf: List[Any] = []

        def flush_eval_group(states):
            """Eval twin of flush_training_group: a full k-group is ONE
            collective eval_many dispatch on every process; a trailing
            partial runs as single collective eval_steps."""
            if not eval_buf:
                return states
            if states is None:
                states = self._trainer.new_metric_states()
            if len(eval_buf) == k and k > 1:
                states = self._trainer.eval_many(
                    self._state,
                    make_global_batch_stack(
                        self._mesh, eval_buf, self._spec.batch_partition),
                    states,
                )
            else:
                for b in eval_buf:
                    states = self._trainer.eval_step(
                        self._state,
                        make_global_batch(
                            self._mesh, b, self._spec.batch_partition),
                        states,
                    )
            eval_buf.clear()
            return states

        from elasticdl_tpu.data.prefetch import _wire_cast

        with prof.span("task", records=end - start):
            # data-wait attribution: blocking on the reader/parse pipeline is
            # this process's OWN input path (exactly what the follower-local
            # exchange exists to surface)
            for host_batch in profile_lib.timed_iter(
                svc.batches(shard, start, end), prof
            ):
                # same bf16 wire compression the single-process worker applies
                # (mask exempted by _wire_cast; cohort reports count by span,
                # not mask, so accounting is unaffected either way)
                if self.cfg.wire_dtype:
                    with prof.phase("h2d"):
                        host_batch = _wire_cast(
                            host_batch, self.cfg.wire_dtype)
                if task_type == pb.TRAINING and self._example_host_batch is None:
                    # the speculative compiler's example input: post-cast, so
                    # neighbor-world programs lower with the real wire dtypes
                    self._example_host_batch = host_batch
                if task_type == pb.TRAINING:
                    if self._state is None:
                        self._ensure_state(make_global_batch(
                            self._mesh, host_batch, self._spec.batch_partition))
                        self._maybe_apply_ctrl_lr()
                    buf.append(host_batch)
                    if len(buf) == k:
                        flush_training_group()
                    continue
                if k > 1 and task_type in (pb.EVALUATION, pb.PREDICTION):
                    # grouped eval/prediction: same collective scan dispatch on
                    # every process, mirroring training groups
                    if self._state is None:
                        self._ensure_state(make_global_batch(
                            self._mesh, host_batch, self._spec.batch_partition))
                        self._maybe_apply_ctrl_lr()
                    if task_type == pb.EVALUATION:
                        eval_buf.append(host_batch)
                        if len(eval_buf) == k:
                            metric_states = flush_eval_group(metric_states)
                    else:
                        pred_buf.append(host_batch)
                        if len(pred_buf) == k:
                            flush_predict_group()
                    continue
                batch = make_global_batch(
                    self._mesh, host_batch, self._spec.batch_partition
                )
                self._ensure_state(batch)
                self._maybe_apply_ctrl_lr()
                if task_type == pb.PREDICTION:
                    outputs = self._trainer.predict_step(self._state, batch)
                    self._process_predictions(outputs, host_batch)
                else:
                    if metric_states is None:
                        metric_states = self._trainer.new_metric_states()
                    metric_states = self._trainer.eval_step(
                        self._state, batch, metric_states
                    )
            flush_training_group()   # trailing partial group (single steps)
            metric_states = flush_eval_group(metric_states)  # trailing partial
            flush_predict_group()                            # trailing partial

        if task_type == pb.TRAINING:
            # COLLECTIVE member-stats exchange at the task boundary (every
            # process reaches this point in lockstep; the task_type gate
            # branches identically everywhere): followers' real step times
            # land on the leader for the next heartbeat's MemberBeats
            self._exchange_member_stats()

        # every process — followers included — samples its own time-series
        # ring at the task boundary (interval-gated: a clock read when not
        # due). The leader additionally samples from its heartbeat thread;
        # followers have no heartbeat, so this is their only cadence.
        from elasticdl_tpu.observability import timeseries as timeseries_lib

        timeseries_lib.get_store().maybe_sample()

        if flags & FLAG_CHECKPOINT:
            mngr = self._checkpoint_manager()
            if mngr is not None and self._state is not None:
                # collective: every process writes its addressable shards
                mngr.save(self._state, wait=True)
                self._last_ckpt_step = self._state.model_version

        if not self.ctx.is_leader:
            return
        report = pb.ReportTaskResultRequest(
            worker_id=self.worker_id, task_id=task_id, success=True,
            records_processed=end - start,
            model_version=(
                self._state.model_version if self._state is not None else 0
            ),
            loss_sum=loss_sum, loss_count=loss_count,
            step_time_sum=step_time_sum, step_count=loss_count,
        )
        try:
            with prof.span("report"):
                self._session.stub.ReportTaskResult(report, timeout=30)
                if task_type == pb.EVALUATION and metric_states is not None:
                    msg = pb.ReportEvaluationMetricsRequest(
                        worker_id=self.worker_id, eval_job_id=eval_job,
                        task_id=task_id,
                    )
                    for name, state in metric_states.items():
                        arr = np.asarray(jax.device_get(state), np.float32)
                        msg.states.append(
                            pb.MetricState(name=name, data=arr.tobytes())
                        )
                    self._session.stub.ReportEvaluationMetrics(msg, timeout=30)
        except Exception as e:
            logger.warning("cohort report failed for task %d: %s", task_id, e)
            # fenced = the restarted master requeued this lease; re-register
            # so the next lease lands, never resend the pre-crash report
            self._session.maybe_reconnect(e)

    def _export_final_model(self) -> None:
        if not self.cfg.output or self._state is None:
            return
        try:
            from elasticdl_tpu.training.export import export_model

            # collective gather (process_allgather) on every process;
            # only the leader writes files
            export_model(
                self._state, self.cfg.output,
                model_def=self.cfg.model_def,
                model_params=self._spec.model_params,
                module_name=self._spec.module_name,
                write_files=self.ctx.is_leader,
            )
        except Exception:
            logger.exception("cohort final export failed")

    # ------------------------------------------------------------------ #

    def _install_sigterm_drain(self) -> None:
        """(Re-)install the preemption handler AFTER world formation:
        `jax.distributed.initialize` registers its own C++ SIGTERM handler
        (xla preemption_notifier), silently replacing anything the
        entrypoint installed earlier — so the drain handler must be
        installed here to win. No-op off the main thread."""
        import signal
        import sys as _sys

        def _on_sigterm(*_):
            if not self.request_preempt():
                _sys.exit(ExitCode.COHORT_EVICTED)

        try:
            signal.signal(signal.SIGTERM, _on_sigterm)
        except ValueError:
            pass

    def run(self) -> int:
        from elasticdl_tpu.common import membership_signal
        from elasticdl_tpu.observability import tracing
        from elasticdl_tpu.observability.http import start_server

        # observability: role + world version on every span/log; when this
        # boot IS a reform (the master's announcement carries a trace id),
        # the boot spans join the master's resize timeline
        role = f"cohort-{self.ctx.process_id}"
        tracing.configure_from_config(
            self.cfg, role=role, world_version=self.ctx.world_version
        )
        # flight recorder: every cohort process gets its own black box
        # (crash/SIGUSR2//debug/flight triggers; flight.py trigger matrix)
        flight_lib.configure_from_config(self.cfg, role=role)
        flight_lib.install_crash_hooks()
        # metrics time series: ring + rolling history for this process;
        # sampled from the leader's heartbeat loop (followers sample at
        # task boundaries via the same singleton)
        from elasticdl_tpu.observability import timeseries as timeseries_lib

        timeseries_lib.configure_from_config(self.cfg, role=role)
        reform_tid = membership_signal.trace_id()
        # a set EDL_METRICS_PORT overrides cfg.metrics_port either way
        metrics_server = start_server(
            role=role, port=self.cfg.metrics_port
        )
        try:
            # goodput: a (re-)forming world's formation + build time IS
            # the cohort flavor's rescale cost — settle (rendezvous) and
            # compile (trainer construction against the warm cache)
            with tracing.span(
                "cohort.world_form", trace_id=reform_tid,
                num_processes=self.ctx.num_processes,
                process_id=self.ctx.process_id,
            ), goodput_lib.get_ledger().phase("rescale", sub="settle"):
                self.ctx.initialize()
        except Exception:
            logger.exception(
                "world formation failed (coordinator %s, process %d/%d)",
                self.ctx.coordinator_addr, self.ctx.process_id,
                self.ctx.num_processes,
            )
            # a formation failure's last seconds (coordinator address,
            # port race, peer set) are postmortem gold — cut the box
            flight_lib.get_recorder().dump("world_form_failed")
            if metrics_server is not None:
                metrics_server.stop()
            return ExitCode.WORLD_FORM_FAILED
        self._install_sigterm_drain()
        try:
            with tracing.span("cohort.build", trace_id=reform_tid), \
                    goodput_lib.get_ledger().phase("rescale", sub="compile"):
                self._build()
            if self.ctx.is_leader:
                # the register RPC carries the reform trace id (when this
                # boot is one) to the master via gRPC metadata — the
                # cross-role join point of the resize timeline
                with tracing.span("cohort.register", trace_id=reform_tid):
                    self._connect()
                self._init_embedding_tier()
                self._session.start_heartbeats(
                    model_version=lambda: self._model_version,
                    stats_payload=self._stats_payload,
                    on_response=self._on_heartbeat_response,
                    request_fields=self._heartbeat_fields,
                )
            backoff = max(0.5, self.cfg.worker_heartbeat_s / 4)
            prof = profile_lib.get_profiler()
            # one iteration is one task turn (lease, broadcast, the task and
            # its report): spans of the device profiler's trace, as in
            # worker.py's loop
            while True:
                with prof.span("task_turn") as turn:
                    leader_ctrl = (
                        self._lease_control()
                        if self.ctx.is_leader
                        else [0] * CTRL_LEN
                    )
                    ctrl = [int(x) for x in self.ctx.broadcast_ints(leader_ctrl)]
                    op = ctrl[0]
                    if self.ctx.is_leader and self._tier is not None:
                        # replica delta sync at the collective poll boundary
                        # (leader-only — the tier is the leader's; cheap
                        # no-op when this cohort replicates nothing)
                        try:
                            self._tier.sync_replicas()
                        except Exception:
                            logger.exception("embedding replica sync failed")
                    if op == OP_NOOP:
                        # jittered on the LEADER only (followers just follow
                        # the broadcast), so idle cohorts de-phase their
                        # polls. Goodput: idle-with-no-task is `lease_wait`.
                        with goodput_lib.get_ledger().phase("lease_wait"), \
                                prof.span("lease.wait"):
                            time.sleep(
                                jittered(backoff) if self.ctx.is_leader
                                else backoff
                            )
                        continue
                    if op == OP_TASK:
                        turn.set_metadata(
                            task_id=ctrl[1], type=pb.TaskType.Name(ctrl[2]))
                        self._run_task(ctrl)
                        # steady state (a task ran): arm the neighbor-world
                        # precompiler so a future reform lands on a warm cache
                        self._maybe_start_speculative_compiler()
                        continue
                    if op in (OP_DONE, OP_ABORT):
                        if op == OP_DONE:
                            self._export_final_model()
                        break

            def finish():
                """Post-loop teardown (runs UNDER the drain checkpoint's
                async write when one is in flight — the overlap that keeps
                the final save off the critical teardown path)."""
                if self._spec_compiler is not None:
                    self._spec_compiler.stop()
                processor = (
                    self._spec.prediction_outputs_processor
                    if self._spec else None
                )
                if processor is not None:
                    # only the leader's processor ever received outputs, but
                    # close() on every process is harmless and guarantees the
                    # leader's buffered tail is flushed (base-class contract)
                    try:
                        processor.close()
                    except Exception:
                        logger.exception(
                            "prediction outputs processor close failed")
                self._shutdown.set()
                if self.ctx.is_leader:
                    self._session.close()

            # the tier's shards drain on EVERY teardown path (the next
            # leader generation restores them bit-exactly, watermarks
            # included) — cheap, atomic per shard, leader-only
            self._drain_embedding_tier()
            if op == OP_ABORT and ctrl[6] & FLAG_CHECKPOINT:
                # preemption drain: one final collective save so the
                # relaunched cohort resumes at the pre-kill step. The write
                # is async and overlapped with the teardown work above —
                # save_overlapped blocks for durability before we return
                # (and before ctx.shutdown tears the world down).
                mngr = self._checkpoint_manager()
                if mngr is not None and self._state is not None:
                    mngr.save_overlapped(self._state, finish)
                    self._last_ckpt_step = self._state.model_version
                    logger.info(
                        "preemption checkpoint saved at step %d "
                        "(write overlapped with teardown)",
                        self._last_ckpt_step,
                    )
                else:
                    finish()
            else:
                finish()
            # ABORT = the master evicted us without job completion (e.g. a
            # heartbeat lapse marked the leader dead and our tasks were
            # requeued): exit EX_TEMPFAIL so the manager relaunches the
            # cohort; a clean 0 would read as success and end all watching.
            return 0 if op == OP_DONE else ExitCode.COHORT_EVICTED
        finally:
            if metrics_server is not None:
                metrics_server.stop()
            tracing.get_tracer().close()
            self.ctx.shutdown()


def run_cohort(cfg: JobConfig) -> int:
    """Build a CohortWorker with full SIGTERM wiring and run it: before
    world formation the handler is a plain EX_TEMPFAIL exit (nothing to
    drain yet); run() upgrades it to the leader drain after
    `jax.distributed.initialize` (which would otherwise clobber it — see
    `_install_sigterm_drain`). The one cohort entrypoint: anything that
    constructs CohortWorker directly gets no pre-formation handler."""
    import signal
    import sys

    worker = CohortWorker(cfg)
    try:
        signal.signal(
            signal.SIGTERM, lambda *_: sys.exit(ExitCode.COHORT_EVICTED)
        )
    except ValueError:
        pass  # not the main thread (tests driving run_cohort in-process)
    return worker.run()
