"""Benchmarks: DeepFM headline + all parity configs + input pipeline, on
the local chip.

The reference publishes no numbers (`BASELINE.json "published": {}`), so the
north-star metric is samples/sec/chip on the DeepFM config. Methodology (see
the note in `_run_steps`): the headline measures the CHIP — steady-state
jitted train steps over rotating device-resident batches — and the input
pipeline (disk → decode → H2D) is measured separately.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": "samples/s/chip", "vs_baseline": N, ...}
Extra keys: per-config sweep (`configs`), pipeline numbers, and — on TPU —
MFU/roofline fields: every model leg reports analytic FLOPs (XLA cost
analysis of the lowered step) -> achieved TFLOP/s -> `mfu_pct` vs the
chip's bf16 peak. EDL_BENCH_FAST=1 skips the sweep (headline + pipeline
only).

A subprocess device probe with a hard timeout runs FIRST (the parent never
initialises a backend, so each leg subprocess gets the chip to itself); a
failed probe or a failed headline leg exits non-zero. All legs are clamped
to one global BUDGET_S deadline measured from process start.
EDL_BENCH_CPU=1 re-points every leg at the CPU backend (dev only; its
numbers are not chip numbers).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

# Baseline for vs_baseline — the round-3 chip measurement (train_many scan
# + scalar readback), which predates PR 1 and this installation. Override
# with EDL_BENCH_BASELINE.
DEFAULT_BASELINE = 260_000.0

# overridable for CPU smoke runs of the full orchestration (EDL_BENCH_CPU)
# and for chip debugging; the defaults are the headline config
BATCH = int(os.environ.get("EDL_BENCH_BATCH", "8192"))
FIELD_VOCAB = int(os.environ.get("EDL_BENCH_FIELD_VOCAB", "100000"))
# 26 fields -> 2.6M-row shared table (~166 MB fp32) at the default
SCAN_STEPS = int(os.environ.get("EDL_BENCH_SCAN_STEPS", "32"))

# Timing methodology: every timed region here (a) ends with a scalar host
# readback (`float(loss)`) that DEPENDS on all dispatched work, so the
# region covers device compute and not just the enqueue, and (b)
# adaptively grows its iteration count until wall time >=
# EDL_BENCH_MIN_WALL_S, keeping the dispatch+readback latency a small
# share of the measurement.
MIN_WALL_S = float(os.environ.get("EDL_BENCH_MIN_WALL_S", "2.5"))

# Chip rooflines for MFU reporting (device_kind substring -> (peak bf16
# dense TFLOP/s, HBM GB/s), public spec-sheet numbers; first match wins,
# so more specific kinds come first). Override the FLOP peak with
# EDL_PEAK_TFLOPS. MFU here = achieved-FLOPs(analytic,
# from the lowered HLO's cost analysis) / bf16 peak — the portable yardstick
# SURVEY §6 asks for since the reference publishes no absolute numbers.
TPU_PEAKS = (
    ("v6", (918.0, 1640.0)),      # Trillium / v6e
    ("v5p", (459.0, 2765.0)),
    ("v5", (197.0, 819.0)),       # v5e / "TPU v5 lite"
    ("v4", (275.0, 1228.0)),
    ("v3", (123.0, 900.0)),
    ("v2", (46.0, 700.0)),
)


def _chip_peaks():
    """Peak bf16 TFLOP/s for this backend; None off-TPU with no override
    (MFU would be meaningless on the CPU mesh). A TPU whose `device_kind`
    matches no TPU_PEAKS entry is an error, not a v5e: an MFU against the
    wrong roofline is worse than none."""
    import jax

    tf_env = os.environ.get("EDL_PEAK_TFLOPS")
    if tf_env:
        return float(tf_env)
    if jax.default_backend() != "tpu":
        return None
    kind = jax.devices()[0].device_kind.lower()
    match = next((peaks for key, peaks in TPU_PEAKS if key in kind), None)
    if match is None:
        raise RuntimeError(
            f"device_kind {kind!r} is not in TPU_PEAKS; add its "
            "spec-sheet peaks (or set EDL_PEAK_TFLOPS)")
    return match[0]


def _mfu_fields(flops_per_step: float, step_s: float) -> dict:
    """MFU/roofline keys for a leg (no `mfu_pct` off-TPU). `flops_per_step`
    is Trainer.train_step_cost's count: the compiled, SPMD-partitioned
    step's, i.e. ONE device's share on a multi-device mesh — already the
    per-chip numerator to hold against the single-chip peak."""
    peak_tf = _chip_peaks()
    if not flops_per_step or not step_s:
        return {}
    achieved_tf = flops_per_step / step_s / 1e12
    out = {
        "gflops_per_step": round(flops_per_step / 1e9, 3),
        "achieved_tflops_per_chip": round(achieved_tf, 3),
    }
    if peak_tf:
        out["mfu_pct"] = round(100.0 * achieved_tf / peak_tf, 3)
    return out


def timed_loop(dispatch, readback, n0, max_iters=100_000):
    """Run `dispatch(i)` n times then `readback()` (must force completion of
    everything dispatched); grow n until the region is long enough to dwarf
    the dispatch+readback latency. Returns (n, seconds)."""
    n = n0
    while True:
        t0 = time.perf_counter()
        for i in range(n):
            dispatch(i)
        readback()
        dt = time.perf_counter() - t0
        if dt >= MIN_WALL_S or n >= max_iters:
            return n, dt
        n = min(max_iters,
                max(n * 2, int(n * MIN_WALL_S * 1.3 / max(dt, 1e-9))))


def _run_steps(trainer, mesh, batches):
    """Steady-state chip throughput via Trainer.train_many: SCAN_STEPS
    jitted steps per dispatch (lax.scan over a stacked batch pytree), so the
    per-dispatch host cost is amortized across K real train steps — the
    chip number, not the dispatch rate. Returns (total_steps, seconds,
    flops per step from Trainer.train_step_cost)."""
    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    reps = -(-SCAN_STEPS // len(batches))
    stacked = shard_batch_stack(
        mesh, (batches * reps)[:SCAN_STEPS],
        getattr(trainer.spec, "batch_partition", None),
    )
    state_box = [trainer.init_state(batches[0])]
    metrics_box = [None]

    def dispatch(i):
        state_box[0], metrics_box[0] = trainer.train_many(
            state_box[0], stacked)

    def readback():
        # scalar host transfer that depends on every dispatched step
        float(metrics_box[0]["loss"][-1])

    dispatch(0)
    readback()      # compile + warmup
    cost = trainer.train_step_cost(state_box[0], batches[0])
    n, dt = timed_loop(dispatch, readback, 2)
    return n * SCAN_STEPS, dt, cost["flops"]


def _make_trainer(mesh, module_name, fn_module, model_params=None):
    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    spec = ModelSpec(
        model=fn_module.custom_model(**(model_params or {})),
        loss=fn_module.loss,
        optimizer=fn_module.optimizer(),
        dataset_fn=None,
        eval_metrics_fn=getattr(fn_module, "eval_metrics_fn", None),
        module_name=module_name,
    )
    return Trainer(spec, mesh)


def bench_deepfm(mesh, np):
    from elasticdl_tpu.common.model_utils import load_module

    deepfm, _ = load_module(os.path.join(REPO_ROOT, "model_zoo"),
                            "deepfm.deepfm.custom_model")
    trainer = _make_trainer(
        mesh, "deepfm.deepfm", deepfm,
        {"field_vocab": FIELD_VOCAB, "hidden": "400,400"},
    )
    batches = []
    for i in range(8):
        r = np.random.RandomState(100 + i)
        batches.append({
            "features": {
                "dense": r.rand(BATCH, 13).astype(np.float32),
                "cat": r.randint(0, 1 << 30, (BATCH, 26)).astype(np.int32),
            },
            "labels": r.randint(0, 2, (BATCH,)).astype(np.int32),
        })
    n, dt, flops_step = _run_steps(trainer, mesh, batches)
    return BATCH * n / dt, _mfu_fields(flops_step, dt / n)


def bench_config(mesh, np, name, batch, make_batches, model_params=None):
    """One parity config: steady-state samples/s + step ms + MFU on the
    chip."""
    from elasticdl_tpu.common.model_utils import load_module

    module, _ = load_module(os.path.join(REPO_ROOT, "model_zoo"),
                            name + ".custom_model")
    trainer = _make_trainer(mesh, name.rsplit(".", 1)[0], module, model_params)
    n, dt, flops_step = _run_steps(trainer, mesh, make_batches(np, batch))
    return {
        "samples_per_sec": round(batch * n / dt, 1),
        "step_ms": round(1e3 * dt / n, 3),
        "batch": batch,
        **_mfu_fields(flops_step, dt / n),
    }


def _image_batches(shape, classes):
    def make(np, batch):
        out = []
        for i in range(4):
            r = np.random.RandomState(i)
            out.append({
                "features": r.rand(batch, *shape).astype(np.float32),
                "labels": r.randint(0, classes, (batch,)).astype(np.int32),
            })
        return out
    return make


def _census_batches(np, batch):
    out = []
    for i in range(4):
        r = np.random.RandomState(i)
        out.append({
            "features": {
                "dense": r.rand(batch, 5).astype(np.float32),
                "cat": r.randint(0, 400, (batch, 9)).astype(np.int32),
            },
            "labels": r.randint(0, 2, (batch,)).astype(np.int32),
        })
    return out


def bench_time_to_auc(mesh, np, target=0.75):
    """A single-chip miniature of the north-star metric (BASELINE.md:
    time-to-AUC on Criteo DeepFM): train the headline DeepFM config on the
    learnable synthetic Criteo stream through the REAL input path (reader →
    batch parser → train_many groups), evaluating a held-out span every
    sweep, until eval AUC >= target. Reports wall seconds from first
    dispatch (compile excluded and reported separately — on the real
    multi-chip target compile amortizes to noise; here it would dominate)."""
    from elasticdl_tpu.common.model_utils import load_module
    from elasticdl_tpu.data.reader import SyntheticDataReader
    from elasticdl_tpu.parallel.mesh import shard_batch_stack
    from elasticdl_tpu.worker.task_data_service import TaskDataService

    deepfm, _ = load_module(os.path.join(REPO_ROOT, "model_zoo"),
                            "deepfm.deepfm.custom_model")
    trainer = _make_trainer(
        mesh, "deepfm.deepfm", deepfm,
        {"field_vocab": FIELD_VOCAB, "hidden": "400,400"},
    )
    n_train, n_eval = BATCH * 64, BATCH * 2
    reader = SyntheticDataReader(
        kind="criteo", num_records=n_train + n_eval, num_shards=8)
    svc = TaskDataService(
        reader, deepfm.dataset_fn("training", reader.metadata), BATCH)
    shard = reader.create_shards()[0][0]

    # stacked once: every AUC evaluation is ONE dispatch (eval_many scan)
    # instead of n_eval/BATCH host round trips
    eval_stacked = shard_batch_stack(
        mesh, list(svc.batches(shard, n_train, n_train + n_eval)))

    def eval_auc(state):
        ms = trainer.eval_many(
            state, eval_stacked, trainer.new_metric_states())
        return float(trainer.metric_results(ms)["auc"])

    group = 8
    box = {"it": iter(svc.batches(shard, 0, n_train))}

    def take_group():
        """Next `group` batches, wrapping the epoch when the stream runs
        dry — always returns exactly `group` (scan length stays constant,
        one compiled program)."""
        batches = []
        while len(batches) < group:
            for b in box["it"]:
                batches.append(b)
                if len(batches) == group:
                    break
            else:
                box["it"] = iter(svc.batches(shard, 0, n_train))
        return batches

    t_compile0 = time.perf_counter()
    batches = take_group()
    state = trainer.init_state(batches[0])
    state, m = trainer.train_many(state, shard_batch_stack(mesh, batches))
    float(m["loss"][-1])                    # compile + first group
    compile_s = time.perf_counter() - t_compile0

    steps = group
    initial_auc = auc = eval_auc(state)
    t0 = time.perf_counter()
    # budget against the timeout this process will actually be KILLED at
    # (the parent passes its possibly-BUDGET_S-clipped value via env),
    # measured from process start — compile + first eval already spent an
    # unknown slice of it, and overrunning loses the whole result
    kill_s = float(os.environ.get(
        "EDL_BENCH_EFFECTIVE_TIMEOUT_S", LEG_TIMEOUT_S))
    deadline = _PROC_T0 + 0.85 * kill_s
    while auc < target and time.perf_counter() < deadline:
        state, m = trainer.train_many(
            state, shard_batch_stack(mesh, take_group()))
        float(m["loss"][-1])
        steps += group
        auc = eval_auc(state)
    return {
        "target_auc": target,
        # compile_and_first_group_s is the one deliberately-timed compile;
        # with the persistent cache it measures deserialization on warm
        # runs — the marker keeps round-log comparisons honest
        "compile_cache_prewarmed":
            os.environ.get("EDL_BENCH_CACHE_PREWARMED") == "1",
        "initial_auc": round(initial_auc, 4),
        "auc": round(auc, 4),
        "seconds_to_auc": round(time.perf_counter() - t0, 3),
        "compile_and_first_group_s": round(compile_s, 2),
        "steps": steps,
        "samples": steps * BATCH,
        "reached": auc >= target,
    }


def _scrape_rescale_metrics(trace_records, analysis=None):
    """Stand up the real /metrics endpoint, scrape it over HTTP, and pull
    out the headline series (compile-cache hit rate, stub retries,
    prefetcher drains). With EDL_BENCH_ARTIFACT_DIR set, the scraped text
    and the resize's trace.jsonl are written there for CI upload."""
    import json as _json
    import urllib.request

    from elasticdl_tpu.observability.http import ObservabilityServer

    # make sure the wire/prefetch metric families exist in this process's
    # registry even though this simulated resize had no live RPCs to count
    import elasticdl_tpu.data.prefetch  # noqa: F401
    import elasticdl_tpu.proto.service  # noqa: F401

    out = {"scraped": False}
    server = ObservabilityServer(role="bench")
    try:
        port = server.start()
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        health = _json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10
        ).read().decode())
        out["scraped"] = True
        out["healthz"] = health.get("status")
        out["series"] = sum(
            1 for ln in text.splitlines()
            if ln and not ln.startswith("#")
        )
        for key in (
            "edl_compile_cache_hit_rate",
            "edl_compile_cache_hits",
            "edl_compile_cache_speculative_compiles",
            "edl_rpc_client_retries_total",
            "edl_prefetch_drains_total",
            "edl_ckpt_handoffs_total",
        ):
            for ln in text.splitlines():
                if ln.startswith(key + " ") or ln.startswith(key + "{"):
                    try:
                        out[key] = float(ln.rsplit(" ", 1)[1])
                    except ValueError:
                        pass
                    break
        art_dir = os.environ.get("EDL_BENCH_ARTIFACT_DIR")
        if art_dir:
            os.makedirs(art_dir, exist_ok=True)
            with open(os.path.join(art_dir, "bench-rescale-trace.jsonl"),
                      "w") as f:
                for rec in trace_records:
                    f.write(_json.dumps(rec) + "\n")
            with open(os.path.join(art_dir, "bench-rescale-metrics.prom"),
                      "w") as f:
                f.write(text)
            if analysis is not None:
                # the analyzer's report next to the raw trace it explains
                # (CI re-runs the CLI over the trace artifact with
                # --strict; this copy is the bench-record-consistent one)
                with open(
                    os.path.join(art_dir, "bench-rescale-analysis.json"),
                    "w",
                ) as f:
                    _json.dump(analysis, f, indent=2, sort_keys=True)
            out["artifacts"] = art_dir
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        server.stop()
    return out


def bench_rescale(mesh, np):
    """Rescale fast path (ISSUE 3): a simulated cohort resize on the local
    mesh (all devices -> half), measuring recovery BOTH ways in the same
    run so the speedup claim is self-contained:

    - cold: the pre-fast-path recovery shape — a fresh trainer on the new
      mesh with a PRIVATE executable cache (every program re-traces, as a
      re-formed process would) restoring state from the latest checkpoint;
    - warm: speculative neighbor compilation beforehand (driven by the
      master's pending-size announcement via the membership signal file),
      live state handoff instead of the checkpoint-restore round trip, and
      the shared executable cache.

    Emits `time_to_recovery_s` (resize signal -> first post-resize step
    done), the cold twin, `recompile_hit_rate` (warm-phase executable-cache
    hit rate), and a bit-exactness check of handoff params against the
    checkpoint-restore path. `mesh` is ignored (the scenario builds its own
    sub-meshes) but keeps the leg signature uniform.

    Observability (ISSUE 4): the whole resize runs under ONE trace id —
    announced through the signal file exactly as the master announces a
    real resize — and the warm recovery is split into `phase.settle`
    (mesh + trainer construction on the new world), `phase.handoff`
    (state movement), and `phase.compile` (first-step dispatch against
    the warm cache). `phases` in the output comes from those spans, the
    scrape block from a live /metrics endpoint; set
    EDL_BENCH_ARTIFACT_DIR to also write trace.jsonl + metrics.prom.

    Cluster health intelligence (ISSUE 7): `critical_path` is the OFFLINE
    trace analyzer (observability/analyzer.py) run on this resize's own
    spans — its phase attribution partitions the rescale root's interval,
    so `critical_path.phase_sum_s` matches `time_to_recovery_s` by
    construction and the critical-path numbers join the perf trajectory
    every round."""
    import tempfile

    import jax

    from elasticdl_tpu.common import membership_signal
    from elasticdl_tpu.common.model_utils import load_module
    from elasticdl_tpu.observability import tracing
    from elasticdl_tpu.parallel import elastic
    from elasticdl_tpu.parallel.mesh import build_mesh
    from elasticdl_tpu.training import compile_cache as cc
    from elasticdl_tpu.training.checkpoint import CheckpointManager
    from elasticdl_tpu.training.trainer import Trainer

    devices = jax.devices()
    n_dev = len(devices)
    new_n = max(1, n_dev // 2)
    if new_n == n_dev:
        return {"error": f"rescale needs >= 2 devices, have {n_dev}"}
    batch_size = BATCH - (BATCH % (n_dev * 2)) or n_dev * 2

    module, _ = load_module(os.path.join(REPO_ROOT, "model_zoo"),
                            "census.wide_deep.custom_model")
    from elasticdl_tpu.training.model_spec import ModelSpec

    spec = ModelSpec(
        model=module.custom_model(), loss=module.loss,
        optimizer=module.optimizer(), dataset_fn=None,
        eval_metrics_fn=getattr(module, "eval_metrics_fn", None),
        module_name="census.wide_deep",
    )
    r = np.random.RandomState(11)
    batch0 = {
        "features": {
            "dense": r.rand(batch_size, 5).astype(np.float32),
            "cat": r.randint(0, 400, (batch_size, 9)).astype(np.int32),
        },
        "labels": r.randint(0, 2, (batch_size,)).astype(np.int32),
    }
    token = "bench-rescale"
    # the PROCESS-GLOBAL cache (cleared for a clean measurement): its
    # counters are what /metrics exports as edl_compile_cache_*, so the
    # scrape below reports the real warm-phase hit rate
    cache = cc.global_cache()
    cache.clear()

    tracing.configure(role="bench", world_version=0)
    trace_id = tracing.new_trace_id()

    def make_trainer(size, use_cache):
        sub = build_mesh({"data": size}, devices[:size])
        return Trainer(spec, sub, cache_token=token, cache=use_cache), sub

    # steady state at full size: init + a few steps
    trainer_a, _ = make_trainer(n_dev, cache)
    state = trainer_a.init_state(batch0)
    for _ in range(2):
        state, logs = trainer_a.train_step(state, batch0)
    float(logs["loss"])  # force completion before the checkpoint

    out = {"world_devices": n_dev, "resized_to_devices": new_n}
    with tempfile.TemporaryDirectory() as tmp:
        mngr = CheckpointManager(os.path.join(tmp, "ckpt"))
        mngr.save(state, wait=True)

        # ---- cold: fresh trainer, private cache, checkpoint restore ----
        cold_cache = cc.CompileCache()
        t0 = time.perf_counter()
        trainer_cold, _ = make_trainer(new_n, cold_cache)
        cold_state = mngr.restore(trainer_cold.init_state(batch0))
        cold_params = jax.device_get(cold_state.params)  # exactness probe
        cold_state, logs = trainer_cold.train_step(cold_state, batch0)
        float(logs["loss"])
        out["cold_recovery_s"] = round(time.perf_counter() - t0, 3)

        # ---- speculative compile, driven by the master's announcement ----
        signal_path = os.path.join(tmp, "membership_signal.json")
        membership_signal.write_signal(
            signal_path, world_size=n_dev, pending_size=new_n,
            trace_id=trace_id)
        out["trace_id"] = trace_id

        def compile_for_size(size):
            if size < 1 or size > n_dev or batch_size % size:
                raise cc.SpeculativeCompiler.SkipSize(
                    f"{size} devices not representable (of {n_dev}, "
                    f"batch {batch_size})"
                )
            t, sub = make_trainer(size, cache)
            abs_state = t.abstract_train_state(batch0)
            t.aot_compile_train_step(
                abs_state, batch0, speculative=True, abstract=True)

        t0 = time.perf_counter()
        speculator = cc.SpeculativeCompiler(
            compile_for_size, n_dev, max_size=n_dev, signal_path=signal_path)
        # the speculative pass joins the resize trace (the real worker path
        # reads the trace id from the signal file the same way)
        with tracing.adopt(trace_id):
            compiled = speculator.precompile_once()
        out["speculative_compile_s"] = round(time.perf_counter() - t0, 3)
        out["speculative_sizes"] = compiled

        # ---- warm: live handoff + shared (pre-warmed) executable cache ----
        handoff = elastic.LiveStateHandoff().capture(state)
        cache.reset_stats()  # hit rate below covers the recovery alone
        t0 = time.perf_counter()
        tracing.set_world_version(1)  # the resize opens world generation 1
        with tracing.span("rescale", trace_id=trace_id,
                          old_devices=n_dev, new_devices=new_n):
            with tracing.span("phase.settle"):
                # membership settling: the new world's mesh + trainer
                trainer_warm, new_mesh = make_trainer(new_n, cache)
            with tracing.span("phase.handoff"):
                warm_state = mngr.restore_or_handoff(
                    trainer_warm.abstract_train_state(batch0), handoff,
                    new_mesh)
                # exactness probe (also forces the handoff's data movement)
                warm_params = jax.device_get(warm_state.params)
            with tracing.span("phase.compile"):
                # cache hit -> dispatch only; miss -> the full re-trace
                warm_state, logs = trainer_warm.train_step(warm_state, batch0)
                float(logs["loss"])
        out["time_to_recovery_s"] = round(time.perf_counter() - t0, 3)
        stats = cache.stats()
        out["recompile_hit_rate"] = round(stats["hit_rate"], 3)
        out["compile_cache"] = {k: round(v, 3) for k, v in stats.items()}
        # per-phase breakdown SOURCED FROM THE SPANS (not re-timed): the
        # same records land in trace.jsonl for the artifact upload
        records = list(tracing.get_tracer().records)
        out["phases"] = tracing.phase_durations(records, trace_id)

        # ---- analyzer-derived critical path (ISSUE 7) ----
        # the offline trace analyzer run on this resize's own spans: the
        # critical path's segments partition the rescale root's interval,
        # so phase_sum_s equals the recovery wall clock by construction —
        # the bench record and the trace artifact can never disagree
        from elasticdl_tpu.observability import analyzer as trace_analyzer

        analysis = trace_analyzer.analyze_records(records, trace_id=trace_id)
        timeline = trace_analyzer.resize_timeline(analysis, trace_id)
        rescale_root = next(
            (r for r in (timeline or {}).get("roots", [])
             if r["name"] == "rescale"),
            None,
        )
        if rescale_root is not None:
            out["critical_path"] = {
                "wall_s": rescale_root["wall_s"],
                "phases": rescale_root["phases"],
                "phase_sum_s": round(
                    sum(rescale_root["phases"].values()), 6),
                "segments": len(rescale_root["critical_path"]),
            }

        # ---- scrape the live /metrics surface (Prometheus text) ----
        out["metrics"] = _scrape_rescale_metrics(records, analysis=analysis)
        mngr.close()

    # live handoff must be bit-exact vs the checkpoint-restore path (the
    # acceptance gate: skipping the restore round trip changes nothing)
    leaves_c = jax.tree_util.tree_leaves(cold_params)
    leaves_w = jax.tree_util.tree_leaves(warm_params)
    out["handoff_params_exact"] = bool(
        len(leaves_c) == len(leaves_w)
        and all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(leaves_c, leaves_w)
        )
    )
    cold, warm = out["cold_recovery_s"], out["time_to_recovery_s"]
    out["recovery_speedup"] = round(cold / warm, 2) if warm else 0.0
    return out


def bench_observability_overhead(mesh, np):
    """Recorder+profiler overhead gate (ISSUE 9, extended by ISSUE 11):
    the same jitted train step measured per-step with the always-on
    observability hot-path instrumentation OFF vs ON. The ON leg mirrors
    (and slightly over-states) what a real worker step pays:

    - step profiler: a data_wait phase + the compute phase with its
      dispatch and readback spans (each an `edl.*` trace annotation) +
      step_done() rolling-window update (observability/profile.py);
    - worker step stats: one observe_step into the heartbeat window;
    - flight ring: the tracer sink attached AND one explicit ring record
      per step (the real worker records nothing per step — spans stay at
      task granularity per EDL404 — so this bounds the ring cost from
      above);
    - time-series ring (ISSUE 11): a maybe_sample() per step against a
      short interval, so real registry snapshots land during the run
      (the real worker samples from its heartbeat thread — per-step
      polling over-states the cost on purpose);
    - skew sketch (ISSUE 11): a Space-Saving update_batch over a
      pre-deduped zipf id chunk per step — the per-pull cost a tier
      worker pays (embedding/sketch.py);
    - request diaries (ISSUE 19): one full diary start/stage/finish
      cycle per step against a live DiaryRecorder — the tail sampler's
      DROP path (the overwhelmingly common case), which is exactly the
      per-call cost every data-plane pull now pays
      (observability/reqtrace.py).

    Emits median/p90 per-step wall time for both modes and
    `overhead_pct` = (on - off) / off over the medians; acceptance: <= 2%.
    Steps are forced individually (float readback) because the PER-STEP
    cost is the measurand — amortizing through train_many would hide it.
    """
    from elasticdl_tpu.common.model_utils import load_module
    from elasticdl_tpu.embedding.sketch import SpaceSaving
    from elasticdl_tpu.observability import flight as flight_lib
    from elasticdl_tpu.observability import profile as profile_lib
    from elasticdl_tpu.observability.health import WorkerStepStats
    from elasticdl_tpu.observability.timeseries import TimeSeriesStore
    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    steps = int(os.environ.get("EDL_BENCH_OBS_STEPS", "200"))
    batch_size = min(BATCH, 1024)
    module, _ = load_module(os.path.join(REPO_ROOT, "model_zoo"),
                            "census.wide_deep.custom_model")
    spec = ModelSpec(
        model=module.custom_model(), loss=module.loss,
        optimizer=module.optimizer(), dataset_fn=None,
        eval_metrics_fn=getattr(module, "eval_metrics_fn", None),
        module_name="census.wide_deep",
    )
    trainer = Trainer(spec, mesh)
    r = np.random.RandomState(3)
    batch = {
        "features": {
            "dense": r.rand(batch_size, 5).astype(np.float32),
            "cat": r.randint(0, 400, (batch_size, 9)).astype(np.int32),
        },
        "labels": r.randint(0, 2, (batch_size,)).astype(np.int32),
    }
    state = trainer.init_state(batch)
    for _ in range(5):                       # compile + warmup
        state, logs = trainer.train_step(state, batch)
    float(logs["loss"])

    # the skew sketch's per-step diet: pre-deduped (unique ids, counts)
    # chunks from a zipf stream — the exact shapes the tier's pull path
    # feeds it (dedupe happens there anyway; the sketch update is the
    # marginal cost under test)
    zipf_ids = (r.zipf(1.3, (steps, 256)) % 65536).astype(np.int64)
    sketch_chunks = [
        np.unique(zipf_ids[i], return_counts=True) for i in range(steps)
    ]

    def run(instrumented: bool):
        nonlocal state
        from elasticdl_tpu.observability import reqtrace as reqtrace_lib
        from elasticdl_tpu.observability.goodput import GoodputLedger
        from elasticdl_tpu.observability.reqtrace import DiaryRecorder

        # the goodput-ledger tee (ISSUE 12) is hot-path cost the real
        # worker pays on every profiler add — it belongs inside the gate
        prof = profile_lib.StepProfiler(ledger=GoodputLedger())
        stats = WorkerStepStats()
        rec = flight_lib.FlightRecorder(ring=4096, role="bench")
        diaries = DiaryRecorder()
        # per-step maybe_sample against a 0.5 s interval: real registry
        # snapshots land mid-run, at ~10x the production cadence (a real
        # worker samples every 5 s from its heartbeat thread, and polls
        # from there too — per-STEP polling here already over-states the
        # clock-read cost)
        tstore = TimeSeriesStore(capacity=256, interval_s=0.5)
        sketch = SpaceSaving(128)
        if instrumented:
            rec.attach_tracing()
        times = []
        try:
            for i in range(steps):
                # times[] captures the WHOLE loop body — the step AND the
                # instrumentation that follows its readback — so the
                # profiler/stats/ring cost actually lands in the measured
                # per-step time (a window closed at the readback would
                # read ~0% overhead no matter how expensive they got)
                t0 = time.perf_counter()
                if instrumented:
                    # the worker loop's own phases, as worker.py opens them:
                    # each a timer, a (locked) add and an `edl.*` annotation
                    with prof.phase("data_wait"):
                        pass
                    with prof.phase("compute", steps=1) as region:
                        with prof.span("compute.dispatch"):
                            state, logs = trainer.train_step(state, batch)
                        with prof.span("compute.readback"):
                            # the scalar readback is the completion barrier
                            # — deliberate per-step sync, it IS the
                            # measurement: edl-lint: disable=EDL201
                            loss = float(logs["loss"])
                    compute_s = region.seconds
                    prof.step_done()
                    stats.observe_step(compute_s, batch_size)
                    rec.record("step", "bench.step", i=i, loss=loss)
                    sketch.update_batch(*sketch_chunks[i])
                    # diaries ON (ISSUE 19): a per-step diary cycle —
                    # start, one timed stage, the tail sampler's O(1)
                    # drop at finish — the per-call cost a data-plane
                    # pull pays under tail-based sampling
                    dd = diaries.start("bench_pull")
                    with reqtrace_lib.stage("wire"):
                        pass
                    diaries.finish(dd)
                    tstore.maybe_sample()
                else:
                    state, logs = trainer.train_step(state, batch)
                    # same barrier, uninstrumented twin:
                    # edl-lint: disable=EDL201
                    float(logs["loss"])
                times.append(time.perf_counter() - t0)
        finally:
            rec.detach_tracing()
        times.sort()
        return times

    # interleave off/on/off/on to cancel drift (CPU boxes throttle), and
    # take the MIN of medians for BOTH modes — each mode gets its
    # quietest window, so box noise subtracts out instead of landing on
    # whichever mode drew the throttled slot (measured 3-14% run-to-run
    # swing on a 1-core sandbox vs the ~1.6% structural cost under test)
    off_a = run(False)
    on_a = run(True)
    off_b = run(False)
    on_b = run(True)

    def med(ts):
        return ts[len(ts) // 2]

    off = min(med(off_a), med(off_b))
    on = min(med(on_a), med(on_b))
    out = {
        "steps_per_mode": steps,
        "median_step_s_off": round(off, 6),
        "median_step_s_on": round(on, 6),
        "p90_step_s_off": round(min(off_a[int(0.9 * steps)],
                                    off_b[int(0.9 * steps)]), 6),
        "p90_step_s_on": round(min(on_a[int(0.9 * steps)],
                                   on_b[int(0.9 * steps)]), 6),
    }
    out["overhead_pct"] = round(100.0 * (on - off) / off, 3) if off else 0.0
    out["gate"] = (
        "<= 2% median step time (ISSUE 9 acceptance; ISSUE 11 adds the "
        "time-series ring + skew sketch, ISSUE 19 the request-diary "
        "cycle, to the ON leg)"
    )
    return out


# ---------------------------------------------------------------------- #
# control-plane throughput (ISSUE 8): a simulated in-process worker swarm
# (threads, no devices) driving register/lease/report/heartbeat against a
# REAL master — journal + dispatcher + membership + servicer behind gRPC.

CP_WORKERS = int(os.environ.get("EDL_BENCH_CP_WORKERS", "64"))
CP_TASKS = int(os.environ.get("EDL_BENCH_CP_TASKS", str(CP_WORKERS * 24)))
CP_BATCH = int(os.environ.get("EDL_BENCH_CP_BATCH", "16"))
CP_GROUP_MS = float(os.environ.get("EDL_BENCH_CP_GROUP_MS", "5"))
CP_HEARTBEATS = int(os.environ.get("EDL_BENCH_CP_HEARTBEATS", "40"))
CP_COHORT = int(os.environ.get("EDL_BENCH_CP_COHORT", "32"))


def _cp_master(tmp, group_ms, n_tasks, journal=True):
    """A real master control plane on an ephemeral port: journal (in
    `tmp`), dispatcher over `n_tasks` single-record tasks, membership,
    servicer, gRPC server. Returns (handles dict) — caller stops/closes."""
    from elasticdl_tpu.master.journal import ControlPlaneJournal
    from elasticdl_tpu.master.membership import Membership
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.proto.service import add_master_servicer, make_server

    j = (ControlPlaneJournal(tmp, group_commit_ms=group_ms)
         if journal else None)
    dispatcher = TaskDispatcher(
        training_shards=[("swarm", 0, n_tasks)], records_per_task=1,
        shuffle=False, task_timeout_s=1e9, journal=j,
    )
    membership = Membership(heartbeat_timeout_s=1e9, journal=j)
    membership.add_death_callback(dispatcher.recover_tasks)
    servicer = MasterServicer(
        dispatcher, membership, None, wait_backoff_s=0.02,
        generation=j.generation if j else 0,
    )
    server = make_server(max_workers=max(32, CP_WORKERS + 4))
    add_master_servicer(server, servicer)
    port = server.add_insecure_port("localhost:0")
    assert port, "could not bind an ephemeral port for the swarm master"
    server.start()
    return {"journal": j, "dispatcher": dispatcher, "membership": membership,
            "servicer": servicer, "server": server, "port": port}


def _cp_channels(port, n_workers):
    """A small shared channel pool (gRPC channels are thread-safe; one
    per simulated worker would burn fds for no fidelity gain)."""
    from elasticdl_tpu.proto.service import make_channel

    return [make_channel(f"localhost:{port}")
            for _ in range(min(8, max(1, n_workers)))]


def _cp_drain(label, group_ms, batch, workers, n_tasks):
    """One swarm cycle in one {commit mode} x {lease batch} cell, split
    into two measured phases so each number isolates one hot path:

    - **dispatch**: `workers` threads lease (max_tasks=batch) until the
      queue is dry — leases/s is THE dispatch-throughput headline (how
      fast the master can hand out work: lock passes, journal commits,
      round-trips all inclusive);
    - **retire**: the same threads report every leased task — reports/s
      measures the ack path (each report is one journaled commit whose
      accepted=True is released only after its fsync).

    Returns throughput + lease latency + a post-drain journal
    commit-latency probe."""
    import tempfile
    import threading

    from elasticdl_tpu.observability import tracing
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
    from elasticdl_tpu.proto.service import MasterStub

    with tempfile.TemporaryDirectory() as tmp:
        m = _cp_master(tmp, group_ms, n_tasks)
        channels = _cp_channels(m["port"], workers)
        lease_lat = [[] for _ in range(workers)]
        held = [[] for _ in range(workers)]   # (wid, task_id) to report
        errors = []

        def dispatch_worker(idx):
            try:
                stub = MasterStub(channels[idx % len(channels)])
                wid = stub.RegisterWorker(
                    pb.RegisterWorkerRequest(worker_name=f"swarm-{idx}"),
                    timeout=30,
                ).worker_id
                while True:
                    t0 = time.perf_counter()
                    resp = stub.GetTask(
                        pb.GetTaskRequest(worker_id=wid, max_tasks=batch),
                        timeout=30,
                    )
                    dt = time.perf_counter() - t0
                    if resp.job_done:
                        return
                    tasks = list(resp.tasks) or [resp.task]
                    if tasks[0].type == pb.WAIT:
                        # queue dry: everything is leased out — this
                        # worker's dispatch phase is over
                        return
                    lease_lat[idx].append(dt)
                    held[idx].extend((wid, t.task_id) for t in tasks)
            except Exception as e:   # a failed worker voids the cell
                errors.append(f"dispatch {type(e).__name__}: {e}")

        def retire_worker(idx):
            try:
                stub = MasterStub(channels[idx % len(channels)])
                for wid, task_id in held[idx]:
                    stub.ReportTaskResult(
                        pb.ReportTaskResultRequest(
                            worker_id=wid, task_id=task_id, success=True,
                        ),
                        timeout=30,
                    )
            except Exception as e:
                errors.append(f"retire {type(e).__name__}: {e}")

        def run_phase(target):
            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=target, args=(i,), daemon=True)
                for i in range(workers)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            return time.perf_counter() - t0

        with tracing.span("control_plane.dispatch", mode=label,
                          workers=workers, lease_batch=batch,
                          group_commit_ms=group_ms):
            dispatch_wall = run_phase(dispatch_worker)
        n_leased = sum(len(h) for h in held)
        with tracing.span("control_plane.retire", mode=label):
            retire_wall = run_phase(retire_worker)

        counts = m["dispatcher"].counts()
        # post-drain probe: K direct commits measure the journal's
        # enqueue-to-durable latency in this mode, uncontended
        probe = []
        for _ in range(50):
            t0 = time.perf_counter()
            m["journal"].append("world_version", version=0).wait()
            probe.append(time.perf_counter() - t0)
        m["server"].stop(None)
        m["journal"].close()
        for ch in channels:
            ch.close()

        lats = sorted(x for per in lease_lat for x in per)
        out = {
            "dispatch_wall_s": round(dispatch_wall, 3),
            "leases_per_sec": round(n_leased / dispatch_wall, 1)
            if dispatch_wall else 0.0,
            "retire_wall_s": round(retire_wall, 3),
            "reports_per_sec": round(n_leased / retire_wall, 1)
            if retire_wall else 0.0,
            "lease_round_trips": len(lats),
            "lease_p50_ms": round(1e3 * _q(lats, 0.5), 3),
            "lease_p99_ms": round(1e3 * _q(lats, 0.99), 3),
            "journal_commit_p50_ms": round(1e3 * _q(sorted(probe), 0.5), 3),
            "journal_commit_p99_ms": round(1e3 * _q(sorted(probe), 0.99), 3),
            "finished_training": counts["finished_training"],
        }
        if errors:
            out["errors"] = errors[:3]
        if counts["finished_training"] != n_tasks or counts["todo"] \
                or counts["doing"]:
            out["accounting_error"] = counts
        return out


def _q(sorted_vals, q):
    from elasticdl_tpu.observability.registry import quantile_sorted

    return quantile_sorted(sorted_vals, q) if sorted_vals else 0.0


def _cp_heartbeats(workers, beats, cohort_size):
    """Heartbeat fan-in: point-to-point (every worker beats for itself,
    stats payload attached — the PR 6 shape) vs cohort-coalesced (ONE
    leader beat carries `cohort_size` MemberBeats). Reports beats/s and
    covered member-beats/s so the O(workers) -> O(cohorts) claim carries
    its own number."""
    import threading

    from elasticdl_tpu.observability import health as health_lib
    from elasticdl_tpu.observability import tracing
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
    from elasticdl_tpu.proto.service import MasterStub

    stats = {"step_p50_ms": 12.0, "records_per_sec": 1000.0,
             "phase": "train"}
    payload = health_lib.encode_stats(stats)
    m = _cp_master("", 0.0, 1, journal=False)
    channels = _cp_channels(m["port"], workers)
    try:
        stub0 = MasterStub(channels[0])
        wids = []
        for i in range(workers):
            wids.append(stub0.RegisterWorker(
                pb.RegisterWorkerRequest(worker_name=f"hb-{i}"),
                timeout=30,
            ).worker_id)

        def beat(idx):
            stub = MasterStub(channels[idx % len(channels)])
            md = ((health_lib.STATS_METADATA_KEY, payload),)
            for _ in range(beats):
                stub.Heartbeat(
                    pb.HeartbeatRequest(worker_id=wids[idx]),
                    timeout=30, metadata=md,
                )

        with tracing.span("control_plane.heartbeats_p2p",
                          workers=workers, beats=beats):
            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=beat, args=(i,), daemon=True)
                for i in range(workers)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            p2p_wall = time.perf_counter() - t0

        # cohort-coalesced: a leader + cohort_size members, ONE beat
        # carrying every member's stats
        resp = stub0.RegisterWorker(
            pb.RegisterWorkerRequest(
                worker_name="hb-leader",
                member_names=[f"hb-leader#p{i}"
                              for i in range(1, cohort_size + 1)],
            ),
            timeout=30,
        )
        members = [
            pb.MemberBeat(worker_id=mid, stats_json=payload)
            for mid in resp.member_ids
        ]
        with tracing.span("control_plane.heartbeats_coalesced",
                          cohort_size=cohort_size, beats=beats):
            t0 = time.perf_counter()
            for _ in range(beats):
                stub0.Heartbeat(
                    pb.HeartbeatRequest(
                        worker_id=resp.worker_id, members=members,
                    ),
                    timeout=30,
                )
            co_wall = time.perf_counter() - t0
        return {
            "point_to_point_beats_per_sec": round(
                workers * beats / p2p_wall, 1),
            "coalesced_rpcs_per_sec": round(beats / co_wall, 1),
            "coalesced_member_beats_per_sec": round(
                beats * cohort_size / co_wall, 1),
            "cohort_size": cohort_size,
            "health_records": len(m["membership"].health_snapshot()),
        }
    finally:
        m["server"].stop(None)
        for ch in channels:
            ch.close()


def _cp_replay_check(group_ms, crash_after):
    """Kill-master replay accounting for one commit mode: a deterministic
    single-threaded client leases+reports against a journaled dispatcher,
    the master dies abruptly (journal.abort — queued group commits drop,
    exactly as SIGKILL) mid-run, a successor replays, and the job drains.
    Returns the applied-span multiset + final counts; the caller asserts
    they are identical across commit modes."""
    import tempfile

    from elasticdl_tpu.master.journal import ControlPlaneJournal
    from elasticdl_tpu.master.membership import Membership
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    n_tasks = 40
    applied = []

    def boot(tmp):
        j = ControlPlaneJournal(tmp, group_commit_ms=group_ms)
        d = TaskDispatcher(
            training_shards=[("replay", 0, n_tasks)], records_per_task=1,
            shuffle=False, task_timeout_s=1e9, journal=j,
        )
        Membership(heartbeat_timeout_s=1e9, journal=j)
        return j, d

    with tempfile.TemporaryDirectory() as tmp:
        j, d = boot(tmp)
        for _ in range(crash_after):
            task = d.get(0)
            applied.append((task.shard_name, task.start, task.end))
            d.report(task.task_id, 0, success=True)
        stranded = d.get(0)            # leased, never reported — the
        j.abort()                      # crash strands it in flight
        j2, d2 = boot(tmp)
        while not d2.finished():
            task = d2.get(0)
            if task is None:
                d2.poke()
                continue
            applied.append((task.shard_name, task.start, task.end))
            d2.report(task.task_id, 0, success=True)
        counts = d2.counts()
        j2.close()
    spans = sorted(applied)
    return {
        "generation": j2.generation,
        "stranded_lease_requeued": stranded is not None,
        "exactly_once": spans == sorted(set(spans)) and len(spans) == n_tasks,
        "counts": {k: counts[k] for k in
                   ("finished_training", "todo", "doing",
                    "failed_permanently")},
        "spans": spans,
    }


def bench_control_plane(mesh=None, np=None):
    """Control-plane throughput (ISSUE 8; ROADMAP 3): the 2x2 matrix
    {per-commit, group-commit} x {lease batch 1, N} over a simulated
    worker swarm, heartbeat fan-in point-to-point vs cohort-coalesced,
    and a kill-master replay-accounting identity check across commit
    modes. `mesh`/`np` are ignored (no devices touched — the leg runs on
    any box); kept for the uniform leg signature."""
    from elasticdl_tpu.observability import tracing

    tracing.configure(role="bench-control-plane")
    trace_id = tracing.new_trace_id()
    out = {
        "workers": CP_WORKERS, "tasks_per_mode": CP_TASKS,
        "lease_batch": CP_BATCH, "group_commit_ms": CP_GROUP_MS,
    }
    modes = {
        "per_commit_b1": (0.0, 1),
        f"per_commit_b{CP_BATCH}": (0.0, CP_BATCH),
        "group_commit_b1": (CP_GROUP_MS, 1),
        f"group_commit_b{CP_BATCH}": (CP_GROUP_MS, CP_BATCH),
    }
    with tracing.adopt(trace_id):
        with tracing.span("control_plane", workers=CP_WORKERS):
            results = {}
            for label, (gms, batch) in modes.items():
                results[label] = _cp_drain(
                    label, gms, batch, CP_WORKERS, CP_TASKS)
            out["modes"] = results
            out["heartbeats"] = _cp_heartbeats(
                CP_WORKERS, CP_HEARTBEATS, CP_COHORT)
            with tracing.span("control_plane.replay_check"):
                per = _cp_replay_check(0.0, crash_after=7)
                grp = _cp_replay_check(CP_GROUP_MS, crash_after=7)
            out["replay_check"] = {
                "per_commit": {k: v for k, v in per.items() if k != "spans"},
                "group_commit": {k: v for k, v in grp.items() if k != "spans"},
                # THE acceptance identity: crash-replay accounting must not
                # depend on the commit mode
                "identical": per["spans"] == grp["spans"]
                and per["counts"] == grp["counts"],
            }
    base = results["per_commit_b1"]["leases_per_sec"]
    best = results[f"group_commit_b{CP_BATCH}"]["leases_per_sec"]
    out["speedup_group_batched_vs_per_commit_b1"] = (
        round(best / base, 2) if base else 0.0
    )
    out["trace_id"] = trace_id

    art_dir = os.environ.get("EDL_BENCH_ARTIFACT_DIR")
    if art_dir:
        os.makedirs(art_dir, exist_ok=True)
        with open(os.path.join(art_dir, "bench-control-plane-trace.jsonl"),
                  "w") as f:
            for rec in tracing.get_tracer().records:
                f.write(json.dumps(rec) + "\n")
    return out


# ---------------------------------------------------------------------- #
# elastic sharded embedding tier (ISSUE 10; ROADMAP 1): sharded vs
# single-host serving throughput, deduped push traffic, and a kill-worker
# resharding run with exactly-once accounting — against a REAL gRPC
# master owning the journal-durable shard map.

ET_SHARDS = int(os.environ.get("EDL_BENCH_ET_SHARDS", "8"))
ET_OWNERS = int(os.environ.get("EDL_BENCH_ET_OWNERS", "8"))
ET_VOCAB = int(os.environ.get("EDL_BENCH_ET_VOCAB", "262144"))
ET_DIM = int(os.environ.get("EDL_BENCH_ET_DIM", "32"))
ET_BATCH = int(os.environ.get("EDL_BENCH_ET_BATCH", "4096"))
ET_LEN = int(os.environ.get("EDL_BENCH_ET_LEN", "16"))
ET_STEPS = int(os.environ.get("EDL_BENCH_ET_STEPS", "8"))
ET_ZIPF = float(os.environ.get("EDL_BENCH_ET_ZIPF", "1.3"))
# read-path legs (ISSUE 13): hot-row cache capacity (rows/table), the
# staleness bound in push-watermark units, replicas per shard, and the
# pull pipeline lookahead. Cache sized ~half the vocab: the zipf(1.3)
# stream's recurring mass fits comfortably; see docs/performance.md
# "Embedding read path" for the sizing rule (hot_id_share-driven).
ET_CACHE = int(os.environ.get("EDL_BENCH_ET_CACHE_ROWS", "131072"))
ET_STALENESS = int(os.environ.get("EDL_BENCH_ET_STALENESS", "16"))
ET_REPLICAS = int(os.environ.get("EDL_BENCH_ET_REPLICAS", "1"))
ET_PIPE = int(os.environ.get("EDL_BENCH_ET_PIPE", "2"))
# simulated wire for the read-path legs: LocalTransport serves from the
# same process, so an owner "RPC" is nearly free here — but the tier's
# deployment regime is RPC-bound (the BENCH_r05 kernel-ceiling vs
# tier-rate gap ISSUE 13 quotes). Every data-plane call sleeps
# base + rows*per_row before serving (sleep releases the GIL, so
# overlap composes exactly like a NIC-bound RPC would); the constants
# are explicit in the bench record and 0/0 turns the wire off.
#
# CALIBRATED (ISSUE 18): the defaults come from the committed
# data_plane baseline's `wire_truth` record — the loopback per-call and
# per-row cost the real gRPC leg MEASURED on a runner of this class —
# instead of the hand-picked 200/1 the model shipped with (the measured
# call cost was ~5x that, which is exactly the gap the fused lanes
# close). Env overrides still win, and a tree without the baseline
# falls back to the old constants.


def _wire_truth_defaults():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bench-baselines", "bench-data-plane.json")
    try:
        with open(path) as f:
            wt = json.load(f)["data_plane"]["wire_truth"]
        return (float(wt["measured_loopback_call_us"]),
                float(wt["measured_loopback_row_us"]))
    except Exception:
        return 200.0, 1.0


_ET_WIRE_DEFAULTS = _wire_truth_defaults()
ET_WIRE_US = float(os.environ.get(
    "EDL_BENCH_ET_WIRE_US", str(_ET_WIRE_DEFAULTS[0])))
ET_WIRE_ROW_US = float(os.environ.get(
    "EDL_BENCH_ET_WIRE_ROW_US", str(_ET_WIRE_DEFAULTS[1])))


def _et_master(tmp, num_shards, replicas=0):
    """A real master control plane owning the embedding shard map:
    journal (in `tmp`), membership with the death->reshard callback
    wired exactly like master/main.py, servicer behind gRPC."""
    from elasticdl_tpu.embedding.sharding import ShardMapOwner
    from elasticdl_tpu.master.journal import ControlPlaneJournal
    from elasticdl_tpu.master.membership import Membership
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.proto.service import add_master_servicer, make_server

    journal = ControlPlaneJournal(tmp)
    dispatcher = TaskDispatcher(
        training_shards=[("et", 0, 1)], records_per_task=1,
        shuffle=False, task_timeout_s=1e9, journal=journal,
    )
    membership = Membership(heartbeat_timeout_s=1e9, journal=journal)
    owner = ShardMapOwner(num_shards, journal=journal,
                          replica_count=replicas)

    def on_death(worker_id):
        alive = [w.worker_id for w in membership.alive_workers()
                 if w.led_by is None]
        if alive and owner.view().owners:
            owner.begin_resharding(alive, dead=[worker_id])

    membership.add_death_callback(on_death)
    servicer = MasterServicer(
        dispatcher, membership, None, generation=journal.generation,
        embedding=owner,
    )
    server = make_server(max_workers=16)
    add_master_servicer(server, servicer)
    port = server.add_insecure_port("localhost:0")
    assert port, "could not bind an ephemeral port for the tier master"
    server.start()
    return {"journal": journal, "membership": membership, "owner": owner,
            "servicer": servicer, "server": server, "port": port,
            "dispatcher": dispatcher}


def _et_full_table(spec, view, transport_):
    """Assemble the dense (vocab, dim) table from its shards — the
    bit-exactness oracle (strided layout: shard s owns ids s, s+S, ...)."""
    import numpy as _np

    out = _np.zeros((spec.vocab, spec.dim), _np.float32)
    for s in range(view.num_shards):
        rows = transport_.store_of(view.owners[s]).extract_shard(
            spec.name, s)["rows"]
        idx = _np.arange(s, spec.vocab, view.num_shards)
        out[idx] = rows[: len(idx)]
    return out


def _et_serving_loops(np):
    """Phase 1+2: single-host tier path (1 shard, no dedupe, per-
    occurrence push — the reference PS protocol) vs the sharded deduped
    path (unique pull, in-step inverse gather, per-unique-row push).
    Pure serving measurement: no master needed, LocalTransport stores in
    host mode (this box serves from host memory; the device mode's
    kernel lane is phase 3's and the TPU run's)."""
    from elasticdl_tpu.embedding import sharding, store, tier, transport

    spec = sharding.TableSpec("users", vocab=ET_VOCAB, dim=ET_DIM, seed=3)
    r = np.random.RandomState(7)
    ids = (r.zipf(ET_ZIPF, (ET_BATCH, ET_LEN)) % ET_VOCAB).astype(np.int64)
    n_ids = ids.size

    def build(num_shards, owners_list, dedupe):
        owners = sharding.assign_round_robin(num_shards, owners_list)
        view = sharding.ShardMapView(
            version=1, num_shards=num_shards, owners=tuple(owners),
            tables=(spec,),
        )
        tr = transport.LocalTransport()
        for o in owners_list:
            st = store.EmbeddingShardStore(o, device=False)
            st.attach(view)
            tr.register(st)
        return tier.EmbeddingTierClient(
            lambda: view, tr, client_id="bench", dedupe=dedupe)

    def timed(fn, steps):
        pulls, pushes = [], []
        fn(pulls, pushes)            # warmup (not recorded)
        pulls.clear(); pushes.clear()
        t0 = time.perf_counter()
        for _ in range(steps):
            fn(pulls, pushes)
        wall = time.perf_counter() - t0
        return {
            "rows_per_sec": round(n_ids * steps / wall, 1),
            "pull_p50_ms": round(_q(sorted(pulls), 0.5) * 1e3, 3),
            "pull_p99_ms": round(_q(sorted(pulls), 0.99) * 1e3, 3),
            "push_p50_ms": round(_q(sorted(pushes), 0.5) * 1e3, 3),
            "push_p99_ms": round(_q(sorted(pushes), 0.99) * 1e3, 3),
        }

    single = build(1, [0], dedupe=False)

    def single_step(pulls, pushes):
        t = time.perf_counter()
        vec = single.pull("users", ids)
        pulls.append(time.perf_counter() - t)
        g = vec.reshape(-1, ET_DIM) * 0.1   # per-OCCURRENCE gradients
        t = time.perf_counter()
        single.push("users", ids, g, scale=-0.01)
        pushes.append(time.perf_counter() - t)

    res_single = timed(single_step, ET_STEPS)

    sharded = build(ET_SHARDS, list(range(ET_OWNERS)), dedupe=True)
    push_stats = {}

    def sharded_step(pulls, pushes):
        t = time.perf_counter()
        rows, inverse, uniq = sharded.pull_unique("users", ids)
        pulls.append(time.perf_counter() - t)
        g = rows * 0.1                      # per-UNIQUE-row gradients
        t = time.perf_counter()
        push_stats.update(sharded.push("users", uniq, g, scale=-0.01))
        pushes.append(time.perf_counter() - t)

    res_sharded = timed(sharded_step, ET_STEPS)
    # deduped push traffic: ids actually sent over the RAW batch ids —
    # pull_unique deduped upstream, so the push's own ids are already
    # unique and its internal ratio would read a vacuous 1.0
    res_sharded["dedupe_ratio"] = round(
        push_stats.get("ids_sent", n_ids) / n_ids, 4)
    # skew telemetry (ISSUE 11): the sharded client's Space-Saving
    # sketch + per-shard load counters measured over the same zipf
    # stream the dedupe ratio comes from — hot_id_share is a GUARANTEED
    # lower bound on the top-K traffic share (the hot-row cache's sizing
    # input; a 0.11 dedupe ratio should read as a large hot share)
    skew = sharded.tier_stats()
    return {
        "ids_per_batch": n_ids,
        "unique_ratio": round(len(np.unique(ids)) / n_ids, 4),
        "zipf_a": ET_ZIPF,
        "hot_id_share": skew.get("emb_hot_id_share", 0.0),
        "shard_load_imbalance": skew.get("emb_shard_imbalance", 0.0),
        "single_host": res_single,
        "sharded": res_sharded,
        "sharded_speedup": round(
            res_sharded["rows_per_sec"] / res_single["rows_per_sec"], 2),
    }


def _sim_wire_transport(inner, call_us, row_us):
    """The shared sim-wire model (embedding/transport.SimWireTransport,
    folded behind the transport contract in ISSUE 15) — the bench's
    read-layer legs and the real gRPC `data_plane` leg are
    interchangeable runs of the same scenario, and the `data_plane`
    leg's `wire_truth` record calibrates these constants against the
    measured loopback RPC cost."""
    from elasticdl_tpu.embedding.transport import SimWireTransport

    return SimWireTransport(inner, call_us, row_us)


def _et_read_path_legs(np):
    """ISSUE 13 acceptance: the three read layers measured one at a time
    on a STREAM of zipf batches (fresh draws per step — cache recurrence
    must come from the distribution, not from replaying one batch):

      off                       PR 10's path: every pull blocks, every
                                read hits the owning shard
      cache                     + worker-local staleness-bounded hot-row
                                cache (write-through keeps it warm)
      cache+replicas            + least-loaded replica reads with
                                delta-synced copies (in-process this
                                attributes correctness + traffic split;
                                the latency win needs a real wire)
      cache+replicas+pipeline   + next batch's pull overlapped with the
                                current step's compute+push

    Each leg reports effective rows/s, the cache hit rate, and the
    goodput ledger's `emb_pull_blocked` delta — the headline being the
    all-layers leg's blocked share vs the off leg's."""
    from collections import deque as _deque

    from elasticdl_tpu.embedding import sharding, store, tier, transport
    from elasticdl_tpu.observability import goodput as goodput_lib

    spec = sharding.TableSpec("users", vocab=ET_VOCAB, dim=ET_DIM, seed=3)
    r = np.random.RandomState(13)
    warm = 2
    stream = [
        (r.zipf(ET_ZIPF, (ET_BATCH, ET_LEN)) % ET_VOCAB).astype(np.int64)
        for _ in range(ET_STEPS + warm)
    ]
    n_ids = stream[0].size
    owners_list = list(range(ET_OWNERS))
    owners = sharding.assign_round_robin(ET_SHARDS, owners_list)
    replica_map = sharding.assign_replicas(
        owners, owners_list, ET_REPLICAS)
    sync_every = max(1, ET_STALENESS // 2)

    def build(read_replicas):
        view = sharding.ShardMapView(
            version=1, num_shards=ET_SHARDS, owners=tuple(owners),
            tables=(spec,),
            replicas=(tuple(tuple(x) for x in replica_map)
                      if read_replicas else ()),
        )
        local = transport.LocalTransport()
        stores = {}
        for o in owners_list:
            st = store.EmbeddingShardStore(o, device=False)
            st.attach(view)
            local.register(st)
            stores[o] = st
        tr = _sim_wire_transport(local, ET_WIRE_US, ET_WIRE_ROW_US)
        def sync_reps():
            for s in range(ET_SHARDS):
                for rep in view.replicas_of(s):
                    stores[rep].sync_replica_from(
                        tr, view.owner_of(s), "users", s)
        if read_replicas:
            sync_reps()
        return view, tr, sync_reps

    def _replica_read_total():
        return sum(
            tier._REPLICA_READS.value(shard=str(s))
            for s in range(ET_SHARDS))

    def measure(name, cache=0, read_replicas=False, pipeline=0):
        view, tr, sync_reps = build(read_replicas)
        client = tier.EmbeddingTierClient(
            lambda: view, tr, client_id=f"bench-{name}",
            cache_rows=cache, cache_staleness=ET_STALENESS,
            read_replicas=read_replicas,
            # sampled sketch feed on EVERY leg (incl. off) so the layer
            # attribution isn't polluted by the GIL-bound telemetry cost
            # the sketch adds uniformly — see tier.py sketch_every note
            sketch_every=max(1, ET_STALENESS // 2),
        )
        pipe = (tier.EmbeddingPullPipeline(client, "users", depth=pipeline)
                if pipeline else None)
        ledger = goodput_lib.get_ledger()
        step_i = [0]
        w_head = np.linspace(-1.0, 1.0, ET_DIM).astype(np.float32)

        def finish(rows, inv, uniq):
            # model-compute stand-in, identical on EVERY leg: the
            # in-step inverse gather (the TierEmbedding lane) + a dense
            # head over the expanded (B*L, dim) activations — fixed
            # shapes, GIL-releasing numpy, the work a pipelined pull
            # rides under. Then per-unique-row grads, tier-side SGD.
            emb = rows[inv.reshape(-1)]
            float(np.tanh(emb @ w_head).mean())
            g = rows * 0.1
            client.push("users", uniq, g, scale=-0.01)
            step_i[0] += 1
            if read_replicas and step_i[0] % sync_every == 0:
                # replica delta sync on the bench thread: in production
                # the REPLICA host pays this (task-boundary sync); the
                # in-process leg bills it here, which only understates
                # the layer's win
                sync_reps()

        def run(batches):
            if pipe is None:
                for ids in batches:
                    rows, inv, uniq = client.pull_unique("users", ids)
                    finish(rows, inv, uniq)
                return
            it = iter(batches)
            window = _deque()
            for ids in it:             # prime the lookahead window
                window.append(ids)
                pipe.submit(ids)
                if len(window) >= pipe.depth:
                    break
            for ids in it:
                window.popleft()
                rows, inv, uniq = pipe.get()
                # submit BEFORE the compute+push: the next pull rides
                # under this step's work (submitting after serializes)
                window.append(ids)
                pipe.submit(ids)
                finish(rows, inv, uniq)
            while window:
                window.popleft()
                rows, inv, uniq = pipe.get()
                finish(rows, inv, uniq)

        run(stream[:warm])
        blocked0 = ledger.snapshot()["categories"]["emb_pull_blocked"]
        cache0 = ((client.cache.hits, client.cache.misses)
                  if client.cache else (0, 0))
        reps0 = _replica_read_total()
        t0 = time.perf_counter()
        run(stream[warm:])
        wall = time.perf_counter() - t0
        blocked = (ledger.snapshot()["categories"]["emb_pull_blocked"]
                   - blocked0)
        out = {
            "rows_per_sec": round(n_ids * ET_STEPS / wall, 1),
            "wall_s": round(wall, 4),
            "pull_blocked_s": round(blocked, 4),
            "pull_blocked_share": round(blocked / wall, 4) if wall else 0.0,
            # reads delivered per second of step-blocking read time —
            # the serving-grade metric the layers exist to move
            "effective_read_rows_per_sec": round(
                n_ids * ET_STEPS / max(1e-9, blocked), 1),
        }
        if client.cache:
            h = client.cache.hits - cache0[0]
            m = client.cache.misses - cache0[1]
            out["cache_hit_rate"] = round(h / max(1, h + m), 4)
            out["cache_stale_evictions"] = int(
                client.cache.stale_evictions)
        if read_replicas:
            out["replica_reads"] = int(_replica_read_total() - reps0)
        if pipe is not None:
            stats = client.tier_stats()
            out["pipeline_depth"] = pipe.depth
            out["read_p99_ms"] = stats.get("emb_read_p99_ms", 0.0)
            out["pull_p99_ms"] = stats.get("emb_pull_p99_ms", 0.0)
            pipe.close()
        client.close()
        return out

    legs = {
        "off": measure("off"),
        "cache": measure("cache", cache=ET_CACHE),
        "cache_replicas": measure(
            "cache-replicas", cache=ET_CACHE, read_replicas=True),
        "cache_replicas_pipeline": measure(
            "all-layers", cache=ET_CACHE, read_replicas=True,
            pipeline=ET_PIPE),
    }
    full = legs["cache_replicas_pipeline"]
    off = legs["off"]
    return {
        "cache_rows": ET_CACHE, "staleness_bound": ET_STALENESS,
        "replicas_per_shard": ET_REPLICAS, "pipeline_depth": ET_PIPE,
        "wire_call_us": ET_WIRE_US, "wire_row_us": ET_WIRE_ROW_US,
        "legs": legs,
        # the three acceptance headlines (ISSUE 13): effective read
        # rows/s = rows delivered per second the STEP was blocked on
        # reads (the emb_pull_blocked goodput category) — the read
        # throughput the critical path experiences; loop_speedup is the
        # whole-loop ratio reported alongside for transparency
        "read_speedup_all_layers": round(
            full["effective_read_rows_per_sec"]
            / off["effective_read_rows_per_sec"], 2),
        "loop_speedup_all_layers": round(
            full["rows_per_sec"] / off["rows_per_sec"], 2),
        "cache_hit_rate": full.get("cache_hit_rate", 0.0),
        "pull_blocked_vs_off": round(
            full["pull_blocked_s"] / max(1e-9, off["pull_blocked_s"]), 4),
    }


class _LostAckTransport:
    """LocalTransport wrapper dropping ONE push ack (store applied, the
    caller never hears) — the deterministic lost-ack the exactly-once
    fence must absorb."""

    def __init__(self, inner, lose_seq):
        self._inner = inner
        self._lose_seq = lose_seq
        self.lost = 0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def push(self, owner, table, shard, local_ids, rows, *, client_id,
             seq, map_version=None, scale=1.0, with_watermark=False):
        applied = self._inner.push(
            owner, table, shard, local_ids, rows, client_id=client_id,
            seq=seq, map_version=map_version, scale=scale,
            with_watermark=with_watermark,
        )
        if seq == self._lose_seq and not self.lost:
            self.lost += 1
            from elasticdl_tpu.embedding.transport import (
                OwnerUnavailableError,
            )

            raise OwnerUnavailableError("injected lost ack")
        return applied


def _et_reshard_scenario(np):
    """Phase 3 (the acceptance scenario): kill an owning worker under a
    REAL gRPC master; the death callback plans minimal moves (journaled
    begin), survivors restore the victim's drained shards from the tier
    checkpoint, confirm over the wire, the master commits (journaled) —
    and every table shard is required to come back BIT-EXACT against an
    unkilled control replica fed the identical push sequence (no lost,
    no double-applied push; one lost ACK is injected on purpose), with
    recovery riding the compile cache (device-mode stores; zero new
    compiles during recovery)."""
    import tempfile

    from elasticdl_tpu.embedding import sharding, store, tier, transport
    from elasticdl_tpu.master.journal import replay_lines
    from elasticdl_tpu.observability import tracing
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
    from elasticdl_tpu.proto.service import MasterStub, make_channel
    from elasticdl_tpu.training import compile_cache as cc

    vocab, dim = 65536, 16
    owners_n = min(4, ET_OWNERS)
    shards_n = ET_SHARDS
    r = np.random.RandomState(11)
    ids = (r.zipf(ET_ZIPF, (1024, 8)) % vocab).astype(np.int64)

    with tempfile.TemporaryDirectory() as tmp:
        m = _et_master(tmp, shards_n)
        spec = sharding.TableSpec("users", vocab=vocab, dim=dim, seed=5)
        m["owner"].register_table(spec)
        channel = make_channel(f"localhost:{m['port']}")
        stub = MasterStub(channel)
        worker_ids = []
        for i in range(owners_n):
            resp = stub.RegisterWorker(
                pb.RegisterWorkerRequest(worker_name=f"et-{i}"))
            worker_ids.append(resp.worker_id)
        shared = transport.LocalTransport()
        runtimes = {}
        for wid in worker_ids:
            # device mode: the jitted gather/scatter lane, so "rides the
            # compile cache" is measurable (host mode has nothing to
            # compile and would prove warmth vacuously)
            os.environ["EDL_EMB_TIER_DEVICE"] = "1"
            try:
                runtimes[wid] = tier.WorkerTierRuntime(
                    stub, wid, checkpoint_dir=tmp, transport=shared)
            finally:
                os.environ.pop("EDL_EMB_TIER_DEVICE", None)
        view0 = runtimes[worker_ids[0]].client.view

        # unkilled control replica: same map, same pushes, applied once
        ctl_tr = transport.LocalTransport()
        for wid in worker_ids:
            st = store.EmbeddingShardStore(wid, device=True)
            st.attach(view0)
            ctl_tr.register(st)
        ctl = tier.EmbeddingTierClient(
            lambda: view0, ctl_tr, client_id="bench-et")

        lossy = _LostAckTransport(shared, lose_seq=3)
        client = tier.EmbeddingTierClient(
            tier.stub_map_fetch(stub, worker_ids[0]), lossy,
            client_id="bench-et",
        )

        def push_step(c, i):
            g = np.random.RandomState(100 + i).rand(
                len(np.unique(ids[ids >= 0])), dim).astype(np.float32)
            uniq = np.unique(ids)
            c.push("users", uniq, g, scale=-0.01)

        # steady state: warm every jitted program (pull + push per shard)
        for i in range(2):
            client.pull_unique("users", ids)
            push_step(client, i)
            push_step(ctl, i)
        cc_before = cc.global_cache().stats()
        dup_before = _et_dup_pushes()

        # --- observe->decide sensor (ISSUE 11 acceptance): the kill
        # must RAISE an alert, edge-triggered once. The engine runs the
        # shipped rule shapes over the client's OWN measured tier stats
        # (fed through timeseries.fleet_series as one synthetic health
        # record per sample — the same aggregation path the master
        # runs); the clock is warped so the burn-rate windows fill in
        # milliseconds, the VALUES are real measurements. The pull-p99
        # page threshold is declared relative to the measured healthy
        # baseline (5x, floor 25 ms) — the bench's tuning of the
        # declarative knob, not a different sensor.
        import threading as _threading

        from elasticdl_tpu.observability.alerts import (
            AlertEngine,
            default_rules,
        )
        from elasticdl_tpu.observability.registry import MetricsRegistry
        from elasticdl_tpu.observability.timeseries import (
            TimeSeriesStore,
            fleet_series,
        )

        art_dir = os.environ.get("EDL_BENCH_ARTIFACT_DIR")
        # the healthy baseline must be the WARM serving p99: the steady-
        # state pulls above paid one-time jit compiles, and a threshold
        # declared relative to compile-laden latencies would be
        # unreachable. Drop them, then measure a few warm pulls.
        client._pull_times.clear()
        for _ in range(4):
            client.pull_unique("users", ids)
        base_stats = client.tier_stats()
        base_p99 = float(base_stats.get("emb_pull_p99_ms", 1.0))
        rules = default_rules()
        for r in rules:
            if r.name == "embedding_pull_p99":
                r.threshold = max(5.0 * base_p99, 25.0)
        alert_store = TimeSeriesStore(
            capacity=512, interval_s=0.0, registry=MetricsRegistry(),
            history_path=(os.path.join(art_dir, "metrics_history.jsonl")
                          if art_dir else None),
        )
        engine = AlertEngine(
            alert_store, rules=rules,
            json_path=(os.path.join(art_dir, "alerts.json")
                       if art_dir else None),
            flight_dump=lambda reason: None,   # the bench has no flight dir
        )

        def sense(stats, t):
            alert_store.sample(now=t, extra=fleet_series(
                [dict(stats, updated_at=t)], now=t))
            engine.evaluate(now=t)

        t_base = time.time()
        for i in range(48):                    # 240 s of healthy history
            sense(base_stats, t_base + 5 * i)
        assert not engine.active(), engine.active()

        victim = worker_ids[-1]
        survivors = [w for w in worker_ids if w != victim]
        kill_pull = {}
        # ISSUE 13: an IN-FLIGHT pipelined pull rides the kill — its
        # result must never be served off the dead/stale map: get()
        # re-issues under the committed map (or the drain hands the
        # batch back for resubmission). Submitted BEFORE the kill so the
        # background pull races the reshard itself.
        pipe = tier.EmbeddingPullPipeline(client, "users", depth=2)
        pipe.submit(ids)

        def _kill_window_pull():
            # a pull issued INTO the dead window: retries (stale map,
            # not-yet-resident shards) until the survivors finish
            # installing — its wall time is the outage as a client saw it
            t = time.perf_counter()
            client.pull_unique("users", ids)
            kill_pull["s"] = time.perf_counter() - t

        t_kill = time.perf_counter()
        with tracing.span("embedding_tier.kill_worker", victim=victim):
            runtimes[victim].drain()          # planned kill: SIGTERM drain
            shared.deregister(victim)
            m["membership"].mark_dead(victim, reason="bench kill")
            puller = _threading.Thread(target=_kill_window_pull)
            puller.start()
            # survivors react (the worker run loop's task-boundary
            # refresh): install from the drain checkpoint, confirm
            for wid in survivors:
                runtimes[wid].on_world_change()
            puller.join(timeout=30)
            # the plan must be COMMITTED now (all moves confirmed)
            final_view = m["owner"].view()
            # the pre-kill pipelined pull: consumed AFTER the reshard —
            # get() must serve rows consistent with the COMMITTED map
            # (re-issued if the background pull ran under the old one)
            rows_p, inv_p, _uniq_p = pipe.get()
            fresh, inv_f, _ = client.pull_unique("users", ids)
            pipeline_rows_match = bool(np.array_equal(
                rows_p[inv_p.reshape(-1)], fresh[inv_f.reshape(-1)]))
            # drain semantics: queued batches come back for resubmission
            # under the fresh map instead of serving stale routing
            pipe.submit(ids)
            drained = pipe.drain()
            for b in drained:
                pipe.submit(b)
            rows_d, inv_d, _ = pipe.get()
            drained_reissued = bool(np.array_equal(
                rows_d[inv_d.reshape(-1)], fresh[inv_f.reshape(-1)]))
            pipe.close()
            # post-recovery traffic proves the tier is serving again —
            # including one injected lost ack, re-sent under the same
            # seq and absorbed by the store's watermark
            push_step(client, 2)              # seq 3: the lost-ack push
            push_step(ctl, 2)
            push_step(client, 3)
            push_step(ctl, 3)
        t_recover = time.perf_counter() - t_kill

        # post-kill sensing: the client's recent pull window now carries
        # the outage pull; feed it until the burn-rate long window is
        # saturated, then keep evaluating — the onset must not repeat
        post_stats = client.tier_stats()
        t_post = t_base + 48 * 5
        for i in range(48):
            sense(post_stats, t_post + 5 * i)
        alert_onsets = [
            h for h in engine.snapshot()["history"]
            if h["transition"] == "firing"
        ]
        engine.write_json()
        cc_after = cc.global_cache().stats()
        dup_after = _et_dup_pushes()

        main_table = _et_full_table(spec, final_view, shared)
        ctl_table = _et_full_table(spec, view0, ctl_tr)
        bit_exact = bool(np.array_equal(main_table, ctl_table))

        # the shard map must also be crash-consistent: replaying the
        # journal file as a successor master would yields the final map
        m["journal"].close()
        with open(os.path.join(tmp, "control", "journal.jsonl")) as f:
            replayed = replay_lines(f.readlines())
        emb = replayed.embedding
        journal_consistent = (
            emb is not None
            and list(emb.owners) == list(final_view.owners)
            and emb.version == final_view.version
            and not emb.reshard_interrupted
        )
        m["server"].stop(None)
        for rt in runtimes.values():
            rt.close()

        return {
            "owners": owners_n, "shards": shards_n,
            "shards_moved": sum(
                1 for s in range(shards_n)
                if view0.owners[s] == victim
                and final_view.owners[s] != victim
            ),
            "recovery_s": round(t_recover, 4),
            "bit_exact": bit_exact,
            "duplicate_pushes_absorbed": int(dup_after - dup_before),
            "lost_acks_injected": lossy.lost,
            "exactly_once": bool(
                bit_exact and lossy.lost >= 1
                and dup_after - dup_before >= 1
            ),
            "reshard_compile_misses": int(
                cc_after["misses"] - cc_before["misses"]),
            "warm_resharding": cc_after["misses"] == cc_before["misses"],
            "journal_map_consistent": journal_consistent,
            "final_map_version": final_view.version,
            "pipelined_pull_consistent_across_reshard":
                pipeline_rows_match,
            "drained_batches_reissued": drained_reissued,
            "drained_batch_count": len(drained),
            "alert": {
                "raised": (alert_onsets[0]["rule"] if alert_onsets
                           else None),
                "onsets": len(alert_onsets),
                "active": [a["rule"] for a in engine.active()],
                "baseline_pull_p99_ms": round(base_p99, 3),
                "killwindow_pull_p99_ms": post_stats.get(
                    "emb_pull_p99_ms", 0.0),
                "killwindow_pull_s": round(kill_pull.get("s", 0.0), 4),
                "pull_p99_threshold_ms": round(
                    max(5.0 * base_p99, 25.0), 3),
            },
        }


def _et_dup_pushes() -> float:
    from elasticdl_tpu.embedding import store as store_lib

    return store_lib._DUP_PUSHES.value()


# layout-controller flip leg (ISSUE 20): geometry of the popularity-flip
# chaos scenario. The head is HUNDREDS of ids wide on purpose — per-shard
# load accounting is deduped, so only a wide head produces the sustained
# shard imbalance the layout controller pages on (a 8-id head is 8 rows
# of deduped traffic no matter how many times it is drawn).
LY_SHARDS = int(os.environ.get("EDL_BENCH_LY_SHARDS", "8"))
LY_WORKERS = int(os.environ.get("EDL_BENCH_LY_WORKERS", "4"))
LY_VOCAB = int(os.environ.get("EDL_BENCH_LY_VOCAB", "65536"))
LY_DIM = int(os.environ.get("EDL_BENCH_LY_DIM", "16"))
LY_BATCH = int(os.environ.get("EDL_BENCH_LY_BATCH", "1024"))
LY_LEN = int(os.environ.get("EDL_BENCH_LY_LEN", "8"))
LY_HEAD = int(os.environ.get("EDL_BENCH_LY_HEAD", "512"))
LY_ZIPF = float(os.environ.get("EDL_BENCH_LY_ZIPF", "1.5"))
LY_PRE_TICKS = int(os.environ.get("EDL_BENCH_LY_PRE_TICKS", "40"))
LY_POST_TICKS = int(os.environ.get("EDL_BENCH_LY_POST_TICKS", "140"))


def _ly_migrate_cost_default() -> float:
    """Seed the layout cost model from the reshard leg's measured
    recovery_s in the checked-in baseline — the blocked-read-seconds a
    shard migration actually bills on this codebase (the EWMA refines
    it online from there)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bench-baselines", "bench-embedding-tier.json")
    try:
        with open(path) as f:
            return float(
                json.load(f)["embedding_tier"]["reshard"]["recovery_s"])
    except Exception:
        return 0.16


class _RowCountTransport:
    """Tallies data-plane pull rows per SERVING worker (owner or
    replica) — the leg's ground-truth per-host read load. Sits under
    the sim wire so it counts exactly the calls that paid wire time;
    replica delta syncs and pushes are deliberately not tallied (the
    imbalance being gated is the READ load a layout action can move)."""

    def __init__(self, inner):
        self._inner = inner
        self.rows = {}

    def take(self):
        out, self.rows = self.rows, {}
        return out

    def _tally(self, owner, n):
        self.rows[owner] = self.rows.get(owner, 0) + int(n)

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def pull(self, owner, table, shard, local_ids, **kw):
        self._tally(owner, (local_ids >= 0).sum())
        return self._inner.pull(owner, table, shard, local_ids, **kw)

    def pull_multi(self, owner, requests, **kw):
        self._tally(owner, sum(
            int((ids >= 0).sum()) for _, _, ids in requests))
        return self._inner.pull_multi(owner, requests, **kw)


def _ly_window_imbalance(owner_rows, t, lo_floor, w=8):
    """max/mean per-host pull rows over the trailing window
    [max(lo_floor, t-w+1), t]. Windowed on purpose: replica routing
    balances at PULL-CALL granularity (a whole shard's rows go to one
    least-loaded host per call, rotating across calls), so a single
    tick always shows one host eating the hot shard — sustained host
    load is what a layout action actually moves. `lo_floor` keeps a
    post-flip window from borrowing healthy pre-flip ticks."""
    lo = max(lo_floor, t - w + 1)
    totals = {}
    for rec in owner_rows[lo:t + 1]:
        for host, n in rec.items():
            totals[host] = totals.get(host, 0) + n
    tot = sum(totals.values())
    if not tot:
        return 1.0
    return round(max(totals.values()) * LY_WORKERS / tot, 4)


def _et_popularity_flip_scenario(np):
    """ISSUE 20 acceptance: a popularity flip mid-run — the zipf head
    remaps to FRESH ids concentrated on a DIFFERENT shard — against the
    real tier + journaled layout controller on a virtual clock, vs a
    static-layout twin fed the bit-identical stream.

    The controller run converges on phase A (replica fan-out + split +
    hot promotion, every decision journaled), then the flip invalidates
    that layout wholesale. The gates: the per-worker read imbalance and
    the per-tick read wall must come back within 1.5x the controller's
    own converged pre-flip level (`layout_recovery_s`, virtual seconds),
    the post-flip trail imbalance must be low (`post_flip_imbalance`),
    and both must be strictly better than the twin measured against the
    SAME healthy envelope — the twin's standing skew is what "a human
    never showed up" looks like.

    The leg runs cache-off: the worker-local cache self-heals a flip on
    its own (read_path leg's territory) and would mask the layout
    signal; here every deduped id pays wire time, so per-owner spread
    (fan-out) and per-call row counts (split) are the whole story. Hot
    promotion still fires and journals — its client-side latency win is
    the cache's, measured in the read_path leg."""
    import dataclasses
    import tempfile

    from elasticdl_tpu.embedding import sharding, store, tier, transport
    from elasticdl_tpu.master import layout_controller as lc
    from elasticdl_tpu.master.journal import (
        ControlPlaneJournal, replay_lines,
    )
    from elasticdl_tpu.observability import alerts as alerts_lib
    from elasticdl_tpu.observability.timeseries import (
        TimeSeriesStore, fleet_series,
    )

    flip_tick = LY_PRE_TICKS
    total_ticks = LY_PRE_TICKS + LY_POST_TICKS
    smooth_w = 8

    def stream_ids(r, phase):
        """One tick's id batch. zipf values < LY_HEAD are the head;
        they map to ids congruent to the phase's hot shard (shard_of is
        id % num_shards) and the PHASE OFFSET makes the post-flip head
        disjoint ids entirely — yesterday's layout knows nothing about
        them. The tail spreads via an odd-multiplier bijection."""
        v = (r.zipf(LY_ZIPF, (LY_BATCH, LY_LEN)) % LY_VOCAB).astype(
            np.int64)
        hot_shard = 0 if phase == 0 else 3
        out = (v * 2654435761 + 97 * (phase + 1)) % LY_VOCAB
        head = v < LY_HEAD
        out[head] = ((v[head] + phase * LY_HEAD) * LY_SHARDS
                     + hot_shard) % LY_VOCAB
        return out

    def run(with_controller):
        r = np.random.RandomState(20)
        with tempfile.TemporaryDirectory() as tmp:
            journal = ControlPlaneJournal(tmp)
            owner = sharding.ShardMapOwner(LY_SHARDS, journal=journal)
            owner.register_table(sharding.TableSpec(
                "emb", vocab=LY_VOCAB, dim=LY_DIM, seed=7))
            owner.bootstrap(list(range(LY_WORKERS)))
            local = transport.LocalTransport()
            stores = {}
            for w in range(LY_WORKERS):
                st = store.EmbeddingShardStore(w, device=False)
                st.attach(owner.view())
                local.register(st)
                stores[w] = st
            counter = _RowCountTransport(local)
            tr = _sim_wire_transport(counter, ET_WIRE_US, ET_WIRE_ROW_US)
            client = tier.EmbeddingTierClient(
                lambda: owner.view(), tr,
                client_id=("bench-layout-ctl" if with_controller
                           else "bench-layout-twin"),
                cache_staleness=4, read_replicas=True,
                fanout_workers=8,
                sketch_window=4 * LY_BATCH * LY_LEN)
            T = [1000.0]
            engine = None
            ctl = None
            if with_controller:
                ts_store = TimeSeriesStore(interval_s=1.0)
                # quarter-scale alert windows: detection latency scales
                # with the scenario, exactly like fleetsim's
                # alert_window_scale
                rules = [dataclasses.replace(
                    rr,
                    window_s=max(1.0, rr.window_s * 0.25),
                    long_window_s=(max(2.0, rr.long_window_s * 0.25)
                                   if rr.long_window_s else 0.0),
                    for_s=rr.for_s * 0.25,
                ) for rr in alerts_lib.default_rules()]
                engine = alerts_lib.AlertEngine(
                    ts_store, rules=rules,
                    flight_dump=lambda reason: None)
                ctl = lc.LayoutController(
                    journal=journal,
                    cost_model=lc.LayoutCostModel(
                        migrate_cost_s=_ly_migrate_cost_default(),
                        horizon_s=60.0),
                    max_shards=2 * LY_SHARDS, max_replicas=2,
                    hot_k=32, cooldown_s=8.0, hold_s=2.0,
                    action_budget=24, clock=lambda: T[0])
                ctl.subscribe(alerts=engine)
                ctl.bind_target(lc.StoreLayoutTarget(owner, stores))
            owner_rows, reads = [], []
            for t in range(total_ticks):
                T[0] = 1000.0 + t
                ids = stream_ids(r, 0 if t < flip_tick else 1)
                client.refresh()
                t0 = time.perf_counter()
                rows, inv, uniq = client.pull_unique("emb", ids)
                reads.append(1e3 * (time.perf_counter() - t0))
                client.push("emb", uniq, rows * 0.1, scale=-0.01)
                # replica delta sync: the replica hosts' task-boundary
                # loop, billed on the bench thread outside the timed
                # read (which only understates the fan-out win)
                view = owner.view()
                for s in range(view.num_shards):
                    for rep in view.replicas_of(s):
                        stores[rep].sync_replica_from(
                            tr, view.owner_of(s), "emb", s)
                owner_rows.append(counter.take())
                if ctl is not None:
                    rec = dict(client.tier_stats())
                    rec["updated_at"] = T[0]
                    ts_store.maybe_sample(
                        now=T[0],
                        extra_fn=lambda rec=rec: fleet_series(
                            [rec], alive_workers=LY_WORKERS,
                            stale_after_s=30.0, now=T[0]))
                    engine.evaluate(now=T[0])
                    ctl.evaluate(now=T[0], workers=[rec])
            pre_read = sum(reads[flip_tick - 10:flip_tick]) / 10.0
            out = {
                "pre_flip_imbalance": _ly_window_imbalance(
                    owner_rows, flip_tick - 1, 0),
                "pre_flip_read_ms": round(pre_read, 3),
                "flip_trail_imbalance": _ly_window_imbalance(
                    owner_rows, total_ticks - 1, flip_tick),
                "flip_trail_read_ms": round(
                    sum(reads[-15:]) / 15.0, 3),
                "_rows": owner_rows, "_reads": reads,
            }
            if ctl is not None:
                snap = ctl.snapshot()
                view = owner.view()
                out["actions_by_kind"] = {
                    k: v for k, v in snap["by_kind"].items() if v}
                out["decisions_journaled"] = snap["decision_records"]
                out["final_num_shards"] = view.num_shards
                out["final_replicas"] = sum(
                    len(view.replicas_of(s))
                    for s in range(view.num_shards))
                out["hot_ids_promoted"] = len(view.hot_ids)
                out["migrate_cost_s"] = snap["migrate_cost_s"]
                # journal replay identity: re-reading the journal must
                # rebuild the FULL decision history (the takeover path)
                journal.close()
                with open(journal.path, encoding="utf-8") as f:
                    rr = replay_lines(f.readlines())
                out["journal_replay_layout_identical"] = bool(
                    rr.layout.records == snap["decision_records"]
                    and rr.layout.by_kind == snap["by_kind"])
            client.close()
            return out

    ctl_run = run(True)
    twin = run(False)

    # one healthy envelope for BOTH runs: 1.5x the controller run's own
    # converged pre-flip level. The twin's pre-flip state is already
    # skewed (nobody ever acted), so "within 1.5x of its own baseline"
    # would let it claim instant recovery from standing damage.
    imb_bound = 1.5 * ctl_run["pre_flip_imbalance"]
    read_bound = 1.5 * ctl_run["pre_flip_read_ms"]

    def recovery_s(res):
        owner_rows, reads = res.pop("_rows"), res.pop("_reads")
        for t in range(flip_tick, total_ticks):
            lo = max(flip_tick, t - smooth_w + 1)
            if (_ly_window_imbalance(owner_rows, t, flip_tick,
                                     w=smooth_w) <= imb_bound
                    and sum(reads[lo:t + 1]) / (t + 1 - lo)
                    <= read_bound):
                return float(t - flip_tick)   # 1 tick = 1 virtual s
        return float(LY_POST_TICKS)           # never recovered (cap)

    ctl_rec = recovery_s(ctl_run)
    twin_rec = recovery_s(twin)
    twin["ticks_to_healthy"] = twin_rec
    return {
        "shards": LY_SHARDS, "workers": LY_WORKERS,
        "head_ids": LY_HEAD, "zipf_a": LY_ZIPF,
        "pre_ticks": LY_PRE_TICKS, "post_ticks": LY_POST_TICKS,
        "healthy_imbalance_bound": round(imb_bound, 4),
        "healthy_read_bound_ms": round(read_bound, 3),
        # the two gated headlines (baseline compare, chaos-layout CI)
        "layout_recovery_s": ctl_rec,
        "post_flip_imbalance": ctl_run["flip_trail_imbalance"],
        "recovered_within_1p5x": bool(ctl_rec < LY_POST_TICKS),
        "strictly_better_than_twin": bool(
            ctl_rec < twin_rec
            and ctl_run["flip_trail_imbalance"]
            < twin["flip_trail_imbalance"]),
        "controller": ctl_run,
        "static_twin": twin,
    }


def bench_embedding_tier(mesh=None, np=None):
    """Elastic sharded embedding tier (ISSUE 10 acceptance): sharded
    lookup+update rows/s vs the single-host tier path, deduped push
    traffic (ids sent / ids in batch), pull/push p50/p99, the ISSUE 13
    read-path legs (hot-row cache / read replicas / pull pipeline,
    attributed per layer over a simulated wire), and the kill-worker
    resharding scenario (bit-exact shards, exactly-once update
    accounting, compile-cache-warm recovery, in-flight pipelined pull
    drained + re-issued). `mesh` is ignored — serving runs host-side;
    phase 3's stores run the jitted device lane on whatever backend is
    up."""
    if np is None:
        import numpy as np
    from elasticdl_tpu.observability import tracing

    tracing.configure(role="bench-embedding-tier")
    # the artifact must carry THIS leg's records only: the tracer's
    # in-memory buffer is process-global (an in-process harness may have
    # buffered earlier records) AND bounded, so an index slice would
    # break once the deque wraps — subscribe a sink for the leg's
    # duration instead (the flight recorder's mechanism)
    leg_records = []

    def _collect(rec):
        leg_records.append(dict(rec))

    tracing.get_tracer().add_sink(_collect)
    trace_id = tracing.new_trace_id()
    try:
        with tracing.adopt(trace_id):
            with tracing.span("embedding_tier", shards=ET_SHARDS):
                serving = _et_serving_loops(np)
                read_path = _et_read_path_legs(np)
                reshard = _et_reshard_scenario(np)
                layout = _et_popularity_flip_scenario(np)
    finally:
        tracing.get_tracer().remove_sink(_collect)
    out = {
        "shards": ET_SHARDS, "owners": ET_OWNERS, "vocab": ET_VOCAB,
        "dim": ET_DIM, "steps": ET_STEPS,
        **serving,
        "read_path": read_path,
        "reshard": reshard,
        "layout": layout,
        "trace_id": trace_id,
    }
    art_dir = os.environ.get("EDL_BENCH_ARTIFACT_DIR")
    if art_dir:
        os.makedirs(art_dir, exist_ok=True)
        with open(os.path.join(art_dir, "bench-embedding-tier-trace.jsonl"),
                  "w") as f:
            for rec in leg_records:
                f.write(json.dumps(rec) + "\n")
    return out


# ---------------------------------------------------------------------- #
# data_plane (ISSUE 15): the partition-tolerant gRPC data plane, chaos leg.
# Real multi-process owners over real gRPC; injected owner partition
# (emb.pull:drop + channel blackhole); hedged reads keep p99 bounded
# while an unhedged control blocks to its deadline; degraded reads are
# attributed by mode; pushes queue-and-journal behind the breaker and
# drain on heal with a seq-fence audit (zero double-applies) and a
# journal replay-identity check.

DP_SHARDS = int(os.environ.get("EDL_BENCH_DP_SHARDS", "4"))
DP_VOCAB = int(os.environ.get("EDL_BENCH_DP_VOCAB", "65536"))
DP_DIM = int(os.environ.get("EDL_BENCH_DP_DIM", "16"))
DP_BATCH = int(os.environ.get("EDL_BENCH_DP_BATCH", "1024"))
DP_LEN = int(os.environ.get("EDL_BENCH_DP_LEN", "8"))
DP_STEPS = int(os.environ.get("EDL_BENCH_DP_STEPS", "40"))
DP_CACHE = int(os.environ.get("EDL_BENCH_DP_CACHE_ROWS", "16384"))
DP_STALENESS = int(os.environ.get("EDL_BENCH_DP_STALENESS", "16"))
DP_DEADLINE_MS = float(os.environ.get("EDL_BENCH_DP_DEADLINE_MS", "500"))
DP_ZIPF = float(os.environ.get("EDL_BENCH_DP_ZIPF", "1.3"))


def _dp_spawn_owner(spec, tmp, name):
    """Launch one owner process (python -m elasticdl_tpu.embedding.
    data_plane --serve) and wait for its bound port."""
    import subprocess

    spec_path = os.path.join(tmp, f"{name}.json")
    port_file = os.path.join(tmp, f"{name}.port")
    spec = dict(spec, port_file=port_file)
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    proc = subprocess.Popen(
        [sys.executable, "-m", "elasticdl_tpu.embedding.data_plane",
         "--serve", spec_path],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        # the owners must NOT inherit the client's chaos schedule: the
        # injected partition is the CLIENT's view of the wire (drops +
        # blackhole), not an owner crash
        env={k: v for k, v in os.environ.items()
             if not k.startswith("EDL_FAULTS")},
    )
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if os.path.exists(port_file):
            with open(port_file) as f:
                return proc, f"127.0.0.1:{int(f.read().strip())}"
        if proc.poll() is not None:
            raise RuntimeError(f"owner process {name} died at boot")
        time.sleep(0.02)
    proc.kill()
    raise RuntimeError(f"owner process {name} never wrote its port")


def bench_data_plane(mesh=None, np=None):
    """ISSUE 15 acceptance scenario (jax-free; real gRPC, real
    processes): healthy baseline -> owner partition (client-side
    emb.pull drops + a channel blackhole that accepts and never
    answers) -> heal. Gates: hedged read p99 under partition <= 3x the
    healthy p99 while the unhedged control blocks to its deadline;
    degraded reads attributed by mode; zero double-applied pushes
    across the heal (seq-fence audit over bit-exact final rows); the
    push-queue journal replays identically; plus a wire-truth record
    calibrating the sim-wire model constants against measured loopback
    RPC cost."""
    import shutil
    import socket
    import tempfile

    if np is None:
        import numpy as np
    from elasticdl_tpu.common import faults
    from elasticdl_tpu.embedding import data_plane as dp
    from elasticdl_tpu.embedding import sharding, tier
    from elasticdl_tpu.embedding.transport import DEGRADED_READS
    from elasticdl_tpu.observability import reqtrace as reqtrace_lib
    from elasticdl_tpu.observability import tracing

    tracing.configure(role="bench-data-plane")
    # fresh diary recorder: the scenario's attribution record must not
    # inherit retained tails from earlier legs in this process
    reqtrace_lib.reset_for_tests()
    rec_tr = reqtrace_lib.get_recorder()
    leg_records = []

    def _collect(rec):
        leg_records.append(dict(rec))

    tracing.get_tracer().add_sink(_collect)

    table = sharding.TableSpec("users", vocab=DP_VOCAB, dim=DP_DIM, seed=5)
    owners = [0] * DP_SHARDS
    replicas = [[1]] * DP_SHARDS
    view = sharding.ShardMapView(
        version=1, num_shards=DP_SHARDS, owners=tuple(owners),
        tables=(table,),
        replicas=tuple(tuple(r) for r in replicas),
    )
    r = np.random.RandomState(29)
    stream = [
        (r.zipf(DP_ZIPF, (DP_BATCH, DP_LEN)) % DP_VOCAB).astype(np.int64)
        for _ in range(2 * DP_STEPS + 8)
    ]
    out = {
        "shards": DP_SHARDS, "vocab": DP_VOCAB, "dim": DP_DIM,
        "steps_per_phase": DP_STEPS, "deadline_budget_ms": DP_DEADLINE_MS,
        "cache_rows": DP_CACHE, "staleness_bound": DP_STALENESS,
    }
    tmp_ctx = tempfile.TemporaryDirectory(prefix="edl-bench-dp-")
    tmp = tmp_ctx.name
    queue_journal = os.path.join(tmp, "emb-push-queue.jsonl")
    procs = []
    blackhole = None
    had_env_faults = bool(os.environ.get(faults.FAULTS_ENV))
    dp_faults_installed = False
    client = ctrl = res = None
    diaries_bundle_path = None
    try:
        base_spec = {
            "num_shards": DP_SHARDS, "owners": owners,
            "replicas": replicas, "version": 1,
            "tables": [{"name": table.name, "vocab": table.vocab,
                        "dim": table.dim, "seed": table.seed,
                        "init_scale": table.init_scale}],
        }
        p0, addr0 = _dp_spawn_owner(dict(base_spec, owner=0), tmp, "owner0")
        procs.append(p0)
        p1, addr1 = _dp_spawn_owner(
            dict(base_spec, owner=1, peer_addrs={"0": addr0},
                 replica_sync_s=0.02),
            tmp, "owner1")
        procs.append(p1)

        budget_s = DP_DEADLINE_MS / 1e3
        res = dp.ResilientTransport(
            dp.GrpcTransport({0: addr0, 1: addr1},
                             default_timeout_s=budget_s),
            policies=dp.default_policies(budget_s),
            staleness_bound=DP_STALENESS,
            view_fn=lambda: view,
            queue_journal=queue_journal,
            breaker_cooldown_s=0.3,
            # partition-detection transient is the read tail's whole
            # cost: two lost races condemn the primary
            breaker_failures=2,
            backoff_base_s=0.005,
            trace_tag="hedged",
        )
        client = tier.EmbeddingTierClient(
            lambda: view, res, client_id="bench-dp",
            cache_rows=DP_CACHE, cache_staleness=DP_STALENESS,
            max_retries=2, retry_backoff_s=0.02,
            sketch_every=8,
        )
        client.wm_probe_every = 4
        # unhedged control: same topology, its own channels, no hedge,
        # no queue — what the partition does to a naive client
        ctrl = dp.ResilientTransport(
            # shm=False: the control is the pure-SOCKET shape — the
            # same-host ring must not quietly rescue it
            dp.GrpcTransport({0: addr0, 1: addr1},
                             default_timeout_s=budget_s, shm=False),
            policies={"pull": dp.CallPolicy(budget_s=budget_s,
                                            max_attempts=1)},
            hedge=False, queue_max=0,
            breaker_failures=10_000,   # never fails fast: pure blocking
            # its diaries are WANTED in the flight bundle (they show
            # what no-hedge costs) but must not pollute the hedged
            # lane's read-tail attribution below
            trace_tag="control",
        )
        ctrl_ids = np.arange(256, dtype=np.int32)

        # shadow accounting for the seq-fence audit: every push's delta,
        # accumulated host-side exactly as the owner should
        shadow = np.zeros((DP_VOCAB, DP_DIM), np.float32)
        push_scale = -0.01

        def run_phase(batches, lats):
            for ids in batches:
                t0 = time.perf_counter()
                rows, inv, uniq = client.pull_unique("users", ids)
                lats.append(time.perf_counter() - t0)
                g = np.full((uniq.shape[0], DP_DIM), 0.1, np.float32)
                real = uniq >= 0
                client.push("users", uniq, g, scale=push_scale)
                shadow[uniq[real]] += push_scale * g[real]

        def p99(lats):
            # nearest-rank (ceil): at small n this is the max — honest
            # for a tail gate (never quietly drops the worst sample)
            s = sorted(lats)
            return s[min(len(s) - 1,
                         max(0, -(-len(s) * 99 // 100) - 1))] if s else 0.0

        # channel warmup + replica-readiness barrier, OUTSIDE the
        # measured phases: the first call on a fresh gRPC channel pays
        # connect + HTTP/2 setup (~40 ms on this box) — a one-off that
        # would otherwise BE both phases' nearest-rank p99 — and the
        # replica owner's background sync loop needs a beat on a loaded
        # box before its copies are resident (hedging into a
        # not-yet-resident replica is a StaleShardMapError, correctly)
        res.shard_watermark(0, "users", 0)
        ctrl.shard_watermark(0, "users", 0)
        deadline = time.monotonic() + 30
        for s in range(DP_SHARDS):
            while True:
                try:
                    res.shard_watermark(1, "users", s, replica=True)
                    break
                except Exception as e:
                    if time.monotonic() >= deadline:
                        raise RuntimeError(
                            f"replica owner never became ready: {e}"
                        ) from e
                    time.sleep(0.05)

        # ---- phase 1: healthy baseline --------------------------------
        healthy_lats = []
        with tracing.span("data_plane.healthy"):
            run_phase(stream[:DP_STEPS], healthy_lats)
        out["healthy_read_p99_ms"] = round(1e3 * p99(healthy_lats), 3)

        # wire truth (satellite): measured loopback RPC cost vs the
        # sim-wire model constants the embedding_tier legs run under
        probe_n = 64
        t0 = time.perf_counter()
        for _ in range(probe_n):
            res.shard_watermark(0, "users", 0)
        call_us = 1e6 * (time.perf_counter() - t0) / probe_n
        big = np.arange(2048, dtype=np.int32)
        small = np.arange(256, dtype=np.int32)
        t0 = time.perf_counter()
        for _ in range(8):
            res.pull(0, "users", 0, big, map_version=1, with_watermark=True)
        t_big = (time.perf_counter() - t0) / 8
        t0 = time.perf_counter()
        for _ in range(8):
            res.pull(0, "users", 0, small, map_version=1,
                     with_watermark=True)
        t_small = (time.perf_counter() - t0) / 8
        row_us = max(0.0, 1e6 * (t_big - t_small) / (2048 - 256))
        out["wire_truth"] = {
            "model_call_us": ET_WIRE_US, "model_row_us": ET_WIRE_ROW_US,
            "measured_loopback_call_us": round(call_us, 1),
            "measured_loopback_row_us": round(row_us, 3),
        }

        # ---- wire-speed throughput legs (ISSUE 18) --------------------
        # raw transport read rate against ONE owner over the same live
        # processes, three stacked lanes so every layer's win is
        # attributed: per-(table, shard) unary pulls (the PR-15 shape:
        # DP_SHARDS calls per round), the FUSED pull_multi over the
        # gRPC socket (1 call per round), and the fused call over the
        # same-host shared-memory ring. Each lane uses its own bare
        # GrpcTransport — no hedging/retry layer, no cache — so the
        # rates are pure wire + codec.
        tp_ids = np.arange(256, dtype=np.int32)
        tp_reqs = [("users", s, tp_ids) for s in range(DP_SHARDS)]
        tp_rows_per_round = DP_SHARDS * int(tp_ids.shape[0])

        def _tp_rate(fn, min_s=0.8):
            fn()                      # warmup (channel / ring setup)
            n = 0
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < min_s:
                fn()
                n += 1
            dt = time.perf_counter() - t0
            return (round(n * tp_rows_per_round / dt, 1),
                    round(1e6 * dt / n, 1))

        t_unary = dp.GrpcTransport({0: addr0, 1: addr1},
                                   default_timeout_s=budget_s, shm=False)
        t_fused = dp.GrpcTransport({0: addr0, 1: addr1},
                                   default_timeout_s=budget_s, shm=False)
        t_shm = dp.GrpcTransport({0: addr0, 1: addr1},
                                 default_timeout_s=budget_s, shm=True)
        try:
            with tracing.span("data_plane.wire_speed"):
                unary_rate, unary_round_us = _tp_rate(lambda: [
                    t_unary.pull(0, "users", s, tp_ids, map_version=1,
                                 with_watermark=True)
                    for s in range(DP_SHARDS)
                ])
                fused_rate, fused_round_us = _tp_rate(
                    lambda: t_fused.pull_multi(0, tp_reqs, map_version=1))
                shm_rate, shm_round_us = _tp_rate(
                    lambda: t_shm.pull_multi(0, tp_reqs, map_version=1))
                shm_ok = bool(getattr(t_shm, "_shm_rings", None))
                # per-CALL wire cost of the ring, payload-free: the
                # batched watermark probe round-trips the same codec +
                # ring with no rows — the fused lanes' per-call floor
                probe_n2 = 256
                t0 = time.perf_counter()
                for _ in range(probe_n2):
                    t_shm.watermark_multi(0, [("users", 0)])
                shm_call_us = 1e6 * (time.perf_counter() - t0) / probe_n2
            out["data_plane_layers"] = {
                "unary_per_table": {
                    "rows_per_s_per_owner": unary_rate,
                    "round_us": unary_round_us,
                    "calls_per_round": DP_SHARDS,
                },
                "fused_grpc": {
                    "rows_per_s_per_owner": fused_rate,
                    "round_us": fused_round_us,
                    "calls_per_round": 1,
                },
                "fused_shm": {
                    "rows_per_s_per_owner": shm_rate,
                    "round_us": shm_round_us,
                    "calls_per_round": 1,
                },
            }
            # the two acceptance headlines: sustained read rows/s
            # against one owner over the full stack, and the measured
            # per-call wire cost on the short-circuit lane
            out["rows_per_s_per_owner"] = shm_rate if shm_ok else fused_rate
            out["wire_per_call_us"] = round(
                shm_call_us if shm_ok else call_us, 1)
            out["coalesce_speedup"] = round(fused_rate / unary_rate, 2)
            out["wire_speed_total_speedup"] = round(
                out["rows_per_s_per_owner"] / unary_rate, 2)
            out["shm_ring_negotiated"] = shm_ok
        finally:
            for t in (t_unary, t_fused, t_shm):
                t.close()

        # ISSUE 19: pre-partition diary snapshot — the partition phase's
        # attribution is the delta past this point, and the healthy
        # tail's dominant stage is recorded for contrast (wire/shm when
        # healthy, hedge/budget under partition)
        pre_part_snap = rec_tr.snapshot()
        out["healthy_dominant_stage"] = rec_tr.dominant_stage()
        t_part0 = time.time()   # diary ts is wall-clock, for filtering

        # ---- phase 2: owner partition ---------------------------------
        # channel blackhole: a socket that accepts and never answers —
        # the connect succeeds, the call hangs to its deadline (the
        # worst partition shape; connection-refused would fail fast)
        blackhole = socket.socket()
        blackhole.bind(("127.0.0.1", 0))
        blackhole.listen(64)
        bh_addr = f"127.0.0.1:{blackhole.getsockname()[1]}"
        res.update_addresses({0: bh_addr})
        ctrl.update_addresses({0: bh_addr})
        if not had_env_faults:
            # the drop half of the injected partition (the CI job may
            # export its own schedule instead)
            faults.install("emb.pull:drop@p=0.05", seed=7)
            dp_faults_installed = True
        deg0 = {m: DEGRADED_READS.value(mode=m)
                for m in ("replica", "cache", "blocked")}
        hedged0 = dp._HEDGED.value()
        part_lats = []
        ctrl_lats = []
        ctrl_blocked = 0
        ctrl_deg_blocked = 0
        with tracing.span("data_plane.partition"):
            for i, ids in enumerate(
                    stream[DP_STEPS:2 * DP_STEPS]):
                t0 = time.perf_counter()
                rows, inv, uniq = client.pull_unique("users", ids)
                part_lats.append(time.perf_counter() - t0)
                g = np.full((uniq.shape[0], DP_DIM), 0.1, np.float32)
                real = uniq >= 0
                client.push("users", uniq, g, scale=push_scale)
                shadow[uniq[real]] += push_scale * g[real]
                if i % 10 == 5:
                    # the unhedged control pays the full deadline.
                    # DEGRADED_READS is process-global and the control
                    # is also a ResilientTransport, so its blocks are
                    # snapshotted out — the main record must attribute
                    # the RESILIENT client's reads only
                    b0 = DEGRADED_READS.value(mode="blocked")
                    t0 = time.perf_counter()
                    try:
                        ctrl.pull(0, "users", 0, ctrl_ids,
                                  map_version=1, with_watermark=True)
                    except Exception:
                        ctrl_blocked += 1
                    ctrl_lats.append(time.perf_counter() - t0)
                    ctrl_deg_blocked += int(
                        DEGRADED_READS.value(mode="blocked") - b0)
        if dp_faults_installed:
            faults.uninstall()
            dp_faults_installed = False
        deg = {m: int(DEGRADED_READS.value(mode=m) - deg0[m])
               for m in ("replica", "cache", "blocked")}
        deg["blocked"] -= ctrl_deg_blocked
        out["read_p99_under_partition_ms"] = round(1e3 * p99(part_lats), 3)
        # the bound: 3x the healthy p99, floored at 60 ms — the hedge
        # transient costs hedge_delay + one replica rtt regardless of
        # how fast the healthy path happened to be on this box, and the
        # meaningful comparison is against the 500 ms deadline the
        # unhedged control pays in full
        bound_s = max(3.0 * p99(healthy_lats), 0.06)
        out["read_p99_bound_ms"] = round(1e3 * bound_s, 1)
        out["read_p99_bounded"] = bool(p99(part_lats) <= bound_s)
        out["hedged_pulls"] = int(dp._HEDGED.value() - hedged0)
        out["degraded_reads"] = deg
        served = deg["replica"] + deg["cache"]
        out["degraded_read_share"] = round(
            served / max(1, served + deg["blocked"]), 4)
        out["degraded_modes_attributed"] = bool(
            deg["replica"] > 0 and deg["cache"] > 0)
        # max, not min: a client-side drop fault can fail one control
        # call fast — the deadline proof is that the BLOCKING shape
        # pays the whole budget, which max() pins deterministically
        out["control_blocked_to_deadline"] = bool(
            ctrl_blocked == len(ctrl_lats) and ctrl_lats
            and max(ctrl_lats) >= 0.8 * budget_s)
        out["control_blocked_p99_ms"] = round(1e3 * p99(ctrl_lats), 3)
        out["push_queue_depth_at_heal"] = res.queue.depth()

        # ---- ISSUE 19: name WHERE the partition p99 went --------------
        # the retained request diaries carry the answer. Three views:
        # the full partition-phase attribution delta (honest: it is
        # wire-heavy, because the pre-breaker push burned its whole
        # deadline on the wire to the dead owner), the READ tail's
        # decomposition over the worst retained pull diaries (the p99
        # the read gate above measures — hedge/budget under partition,
        # wire/shm when healthy), and the incident CLI's slow_calls
        # section over the scenario's own flight bundle.
        part_snap = rec_tr.snapshot()
        part_attr = {}
        for s in reqtrace_lib.STAGES:
            dv = (part_snap["attribution"].get(s, 0.0)
                  - pre_part_snap["attribution"].get(s, 0.0))
            if dv > 0:
                part_attr[s] = round(dv, 6)
        part_wall = (part_snap["slow_wall_s"]
                     - pre_part_snap["slow_wall_s"])
        part_named = {s: v for s, v in part_attr.items() if s != "other"}
        out["p99_attribution"] = part_attr
        out["p99_attribution_known_share"] = (
            round(sum(part_named.values()) / part_wall, 4)
            if part_wall > 0 else 0.0)

        def _dominant(stages):
            named = {s: v for s, v in stages.items()
                     if s != "other" and v > 0} or dict(stages)
            return (max(sorted(named), key=lambda s: named[s])
                    if named else None)

        part_reads = sorted(
            (c for c in rec_tr.retained()
             if c["ts"] >= t_part0 and c["op"] in ("pull", "pull_multi")
             # the unhedged control's deadline-blocked pulls are
             # wire-by-construction — the read gate above measures the
             # HEDGED lane's p99, so its tail is the one decomposed
             and (c.get("meta") or {}).get("tag") != "control"),
            key=lambda c: c["wall_s"], reverse=True)[:8]
        read_attr = {}
        for c in part_reads:
            for s, v in c["stages"].items():
                read_attr[s] = read_attr.get(s, 0.0) + v
        dom_read = _dominant(read_attr)
        out["p99_read_attribution"] = {
            s: round(v, 6) for s, v in sorted(read_attr.items())}
        out["p99_read_dominant_stage"] = dom_read
        # only assert the signature when the scenario's OWN fault
        # schedule ran — a CI-exported schedule may shape the tail
        # differently (e.g. injected wire delays)
        out["p99_dominant_is_hedge_or_budget"] = bool(
            dom_read in ("hedge", "budget_wait", "breaker")
            or had_env_faults)
        # the sum-to-wall invariant, over EVERY retained diary: the
        # per-stage decomposition must account for the whole wall
        worst_err = 0.0
        for c in rec_tr.retained():
            if c["wall_s"] > 0:
                worst_err = max(
                    worst_err,
                    abs(sum(c["stages"].values()) - c["wall_s"])
                    / c["wall_s"])
        out["p99_attribution_worst_error_pct"] = round(
            100.0 * worst_err, 4)
        out["p99_attribution_sums_to_wall"] = bool(worst_err <= 0.01)

        # incident CLI over the scenario's own flight bundle: the
        # slow_calls section must exist, render the retained diaries,
        # contain a read whose own dominant stage is the hedge/budget
        # machinery, and pass the strict diary checks
        from elasticdl_tpu.observability import flight as flight_lib
        from elasticdl_tpu.observability import incident as incident_lib
        fbundle = flight_lib.FlightRecorder(
            ring=64, role="bench-data-plane").bundle("partition scenario")
        diaries_bundle_path = os.path.join(
            tmp, "flight-bench-data-plane.json")
        with open(diaries_bundle_path, "w") as f:
            json.dump(fbundle, f, default=repr)
        inc_report = incident_lib.correlate([diaries_bundle_path])
        sc = inc_report.get("slow_calls") or {}
        out["incident_slow_calls_dominant"] = sc.get("dominant_stage")
        out["incident_slow_calls_retained"] = sc.get("retained")
        out["incident_names_read_tail_stage"] = any(
            c.get("op") in ("pull", "pull_multi")
            and _dominant(c.get("stages") or {}) in (
                "hedge", "budget_wait", "breaker")
            for c in sc.get("calls") or [])
        diary_viol = [v for v in inc_report.get("strict_violations") or []
                      if "diary" in str(v.get("problem", ""))]
        out["incident_diary_strict_clean"] = not diary_viol

        # ---- phase 3: heal + drain + audits ---------------------------
        res.update_addresses({0: addr0})
        time.sleep(0.4)    # breaker cooldown elapses
        with tracing.span("data_plane.heal"):
            drained = res.drain_queued()
        out["queued_pushes_drained"] = drained
        out["push_queue_empty_after_heal"] = res.queue.depth() == 0
        # a few post-heal steps prove the path is direct again
        heal_lats = []
        run_phase(stream[2 * DP_STEPS:2 * DP_STEPS + 8], heal_lats)
        out["healed_read_p99_ms"] = round(1e3 * p99(heal_lats), 3)

        # seq-fence audit: the owner's final rows must equal the
        # deterministic init + EVERY push applied exactly once (the
        # shadow) — a double-applied drain or a lost queued push would
        # break bit-level equality
        from elasticdl_tpu.embedding.store import _init_shard_rows

        max_err = 0.0
        wm_total = 0
        for s in range(DP_SHARDS):
            payload = res.fetch_shard(0, "users", s)
            wm_total += int(payload["wm"])
            init = _init_shard_rows(table, s, DP_SHARDS)
            shard_ids = np.arange(s, DP_VOCAB, DP_SHARDS)
            expect = init[: shard_ids.shape[0]] + shadow[shard_ids]
            max_err = max(max_err, float(
                np.abs(payload["rows"][: shard_ids.shape[0]]
                       - expect).max()))
        pushes_issued = 2 * DP_STEPS + 8
        out["seq_fence_max_row_error"] = round(max_err, 6)
        out["zero_double_applied_pushes"] = bool(
            max_err < 1e-4 and wm_total == pushes_issued * DP_SHARDS)
        out["owner_watermark_total"] = wm_total
        out["pushes_issued"] = pushes_issued

        # journal replay identity: the enqueue stream retired exactly,
        # in order, as the drain stream
        replayed = dp.PushQueue.replay_journal(queue_journal)
        enq = [(e["client_id"], e["seq"], e["shard"])
               for e in replayed["enqueued"]]
        drn = [(e["client_id"], e["seq"], e["shard"])
               for e in replayed["drained"]]
        out["journal_enqueued"] = len(enq)
        out["journal_replays_identically"] = bool(
            enq and enq == drn and drained == len(drn))

    finally:
        if dp_faults_installed:
            # a failure between install and the post-phase uninstall
            # must not leak a process-global 5% pull-drop rule into
            # later legs/tests
            faults.uninstall()
        for closeable in (client, ctrl, res):
            if closeable is not None:
                try:
                    closeable.close()
                except Exception:
                    pass
        tracing.get_tracer().remove_sink(_collect)
        if blackhole is not None:
            blackhole.close()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=5)
            except Exception:
                p.kill()
        art_dir = os.environ.get("EDL_BENCH_ARTIFACT_DIR")
        if art_dir:
            os.makedirs(art_dir, exist_ok=True)
            with open(os.path.join(art_dir,
                                   "bench-data-plane-trace.jsonl"),
                      "w") as f:
                for rec in leg_records:
                    f.write(json.dumps(rec) + "\n")
            if os.path.exists(queue_journal):
                shutil.copyfile(
                    queue_journal,
                    os.path.join(art_dir, "bench-data-plane-pushes.jsonl"))
            if diaries_bundle_path and os.path.exists(diaries_bundle_path):
                # the retained request diaries ride the flight bundle —
                # CI uploads this and runs the incident CLI --strict
                # over it (ISSUE 19)
                shutil.copyfile(
                    diaries_bundle_path,
                    os.path.join(art_dir, "flight-bench-data-plane.json"))
            with open(os.path.join(art_dir,
                                   "bench-data-plane.health.json"),
                      "w") as f:
                json.dump({"role": "bench-data-plane",
                           "record": {k: v for k, v in out.items()
                                      if not k.startswith("_")}},
                          f, indent=1, sort_keys=True, default=repr)
        tmp_ctx.cleanup()
    return out


def bench_host_pipeline(np):
    """Host half of the input path ONLY — disk → contiguous span read →
    binary decode — with no JAX backend touched anywhere (verified: the
    reader/parser/task-data-service modules contain zero jax calls): the
    half of the system that doesn't need the chip."""
    import tempfile

    from elasticdl_tpu.data import parsing as parsing_lib
    from elasticdl_tpu.data.reader import FixedLenBinDataReader
    from elasticdl_tpu.worker.task_data_service import TaskDataService

    n_pipe = BATCH * 24
    r = np.random.RandomState(7)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "criteo.cbin")
        with open(path, "wb") as f:
            f.write(parsing_lib.criteo_bin_encode(
                r.randint(0, 2, n_pipe).astype(np.int32),
                r.rand(n_pipe, 13).astype(np.float32),
                r.randint(0, 1 << 31, (n_pipe, 26)).astype(np.int32),
            ))
        reader = FixedLenBinDataReader(
            path, record_bytes=parsing_lib.criteo_bin_record_bytes()
        )
        svc = TaskDataService(
            reader, parsing_lib.criteo_bin_batch_parser(), BATCH
        )
        for _ in svc.batches(path, 0, BATCH):        # warm page cache
            pass
        t1 = time.perf_counter()
        for _ in svc.batches(path, 0, n_pipe):
            pass
        host_sps = n_pipe / (time.perf_counter() - t1)
    return {"pipeline_host_samples_per_sec": round(host_sps, 1)}


def bench_pipeline(mesh, np):
    """FULL input path: fixed-width .cbin shard on disk → contiguous span
    read → memcpy-speed binary decode → async H2D with bf16 wire cast. Text
    parsing is ingest-time only (parsing.convert_criteo_tsv), exactly like
    the reference's RecordIO conversion, so it is not in the timed region."""
    import tempfile

    import jax

    from elasticdl_tpu.data import parsing as parsing_lib
    from elasticdl_tpu.data.prefetch import prefetch_to_device
    from elasticdl_tpu.data.reader import FixedLenBinDataReader
    from elasticdl_tpu.worker.task_data_service import TaskDataService

    n_pipe = BATCH * 24
    r = np.random.RandomState(7)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "criteo.cbin")
        with open(path, "wb") as f:
            f.write(parsing_lib.criteo_bin_encode(
                r.randint(0, 2, n_pipe).astype(np.int32),
                r.rand(n_pipe, 13).astype(np.float32),
                r.randint(0, 1 << 31, (n_pipe, 26)).astype(np.int32),
            ))
        reader = FixedLenBinDataReader(
            path, record_bytes=parsing_lib.criteo_bin_record_bytes()
        )
        svc = TaskDataService(
            reader, parsing_lib.criteo_bin_batch_parser(), BATCH
        )
        import jax.numpy as jnp

        def flush(batch):
            # scalar readback through one leaf: completion barrier for the
            # H2D chain (block_until_ready is unreliable here — see
            # MIN_WALL_S note)
            return float(jnp.sum(batch["labels"].astype(jnp.float32)))

        warm = next(iter(prefetch_to_device(
            mesh, svc.batches(path, 0, BATCH), depth=2, cast="bfloat16"
        )))
        flush(warm)

        # host half alone (decode, no device link): shows which side bounds
        t1 = time.perf_counter()
        for _ in svc.batches(path, 0, n_pipe):
            pass
        host_sps = n_pipe / (time.perf_counter() - t1)

        t1 = time.perf_counter()
        last = None
        for dbatch in prefetch_to_device(
            mesh, svc.batches(path, 0, n_pipe), depth=2, cast="bfloat16"
        ):
            last = dbatch
        flush(last)
        pipeline_sps = n_pipe / (time.perf_counter() - t1)
    return pipeline_sps, host_sps


# ---------------------------------------------------------------------- #
# fleet goodput ledger (ISSUE 12): a scripted scenario — steady train ->
# injected straggler -> kill-worker rescale -> recover — over the REAL
# dispatcher+journal and real per-worker GoodputLedgers, asserting the
# ledger's total-attribution invariant against independently measured
# wall clock and that the wasted-work bill lands where the scenario put
# it. Jax-free and device-free: `python bench.py goodput` runs anywhere.

def _ledger_stub_membership(snaps):
    """A Membership stand-in over frozen in-thread GoodputLedger
    snapshots, in heartbeat-payload shape via the ONE exported key
    schema — shared by the goodput and autoscale legs so the
    ledger-to-payload shim cannot drift between them (a dropped phase
    key would silently skew both legs' fleet fractions)."""
    from elasticdl_tpu.observability import goodput as goodput_lib

    def payload_from(snap):
        out_p = {"gp_wall_s": round(snap["wall_s"], 3)}
        for cat, key in goodput_lib._PAYLOAD_KEYS.items():
            v = snap["categories"].get(cat, 0.0)
            if v > 0:
                out_p[key] = round(v, 3)
        return out_p

    class _StubMembership:
        def health_snapshot(self):
            now = time.time()
            return [
                dict(payload_from(snaps[w]), worker_id=w, updated_at=now)
                for w in sorted(snaps)
            ]

    return _StubMembership()


GP_WORKERS = int(os.environ.get("EDL_BENCH_GP_WORKERS", "3"))
GP_TASKS = int(os.environ.get("EDL_BENCH_GP_TASKS", "18"))
GP_RECORDS_PER_TASK = int(os.environ.get("EDL_BENCH_GP_RECORDS", "64"))
GP_STEPS_PER_TASK = 4
#: simulated phase sleeps (seconds) — small enough for CI, large enough
#: that scheduler jitter stays well under the 1% attribution gate
GP_DATA_WAIT_S = 0.002
GP_H2D_S = 0.001
GP_COMPUTE_S = 0.004
GP_STRAGGLE_EXTRA_S = 0.012
GP_RESCALE_S = {"settle": 0.005, "handoff": 0.010, "compile": 0.015}


def bench_goodput(mesh=None, np=None):
    """Fleet goodput scenario (ISSUE 12 acceptance): per-worker category
    seconds must sum to measured wall clock within 1%, the injected
    straggler must surface in `train_compute`, the killed worker's
    requeued lease must bill nonzero `worker_died` wasted records, the
    survivors must book nonzero `rescale` seconds, and the journal must
    replay the whole wasted-work bill identically. The headline number
    is the fleet goodput fraction. `mesh`/`np` ignored (uniform leg
    signature; no devices touched)."""
    import tempfile
    import threading

    from elasticdl_tpu.master.journal import ControlPlaneJournal, replay_lines
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.observability import goodput as goodput_lib
    from elasticdl_tpu.observability import profile as profile_lib
    from elasticdl_tpu.observability import tracing

    tracing.configure(role="bench-goodput")
    trace_id = tracing.new_trace_id()

    n_workers = max(2, GP_WORKERS)
    killed_wid = n_workers - 1
    straggler_wid = n_workers - 2
    total_records = GP_TASKS * GP_RECORDS_PER_TASK

    out = {
        "workers": n_workers, "tasks": GP_TASKS,
        "records_per_task": GP_RECORDS_PER_TASK,
        "straggler_worker": straggler_wid, "killed_worker": killed_wid,
    }

    killed_event = threading.Event()     # the victim abandoned its lease
    rescale_event = threading.Event()    # survivors must pay a rescale
    abandoned = {}                       # task_id the victim walked off with

    with tempfile.TemporaryDirectory() as tmp:
        journal = ControlPlaneJournal(tmp)
        dispatcher = TaskDispatcher(
            training_shards=[("train", 0, total_records)],
            records_per_task=GP_RECORDS_PER_TASK,
            num_epochs=1, shuffle=False, task_timeout_s=600.0,
            journal=journal,
        )

        walls = {}
        snaps = {}

        def run_worker(wid):
            ledger = goodput_lib.GoodputLedger()
            prof = profile_lib.StepProfiler(ledger=ledger)
            t0 = time.monotonic()
            tasks_done = 0
            rescaled = False
            straggling = False
            while True:
                if (
                    rescale_event.is_set() and wid != killed_wid
                    and not rescaled
                ):
                    # the kill-worker rescale, reacted to at a task
                    # boundary: settle/handoff/compile, exactly like a
                    # real in-place rescale bills them
                    for sub, dur in GP_RESCALE_S.items():
                        with ledger.phase("rescale", sub=sub):
                            time.sleep(dur)
                    rescaled = True
                task = dispatcher.get(wid)
                if task is None:
                    if dispatcher.finished():
                        break
                    with ledger.phase("lease_wait"):
                        time.sleep(0.002)
                    continue
                if wid == killed_wid and tasks_done >= 2:
                    # the kill: walk off mid-task with the lease held —
                    # the master's death callback requeues it and bills
                    # worker_died wasted records
                    abandoned["task_id"] = task.task_id
                    abandoned["records"] = task.num_records
                    killed_event.set()
                    break
                straggling = (
                    wid == straggler_wid and 2 <= tasks_done <= 4
                )
                for _ in range(GP_STEPS_PER_TASK):
                    with prof.phase("data_wait"):
                        time.sleep(GP_DATA_WAIT_S)
                    with prof.phase("h2d"):
                        time.sleep(GP_H2D_S)
                    step_t0 = time.perf_counter()
                    time.sleep(
                        GP_COMPUTE_S
                        + (GP_STRAGGLE_EXTRA_S if straggling else 0.0)
                    )
                    prof.add("compute", time.perf_counter() - step_t0)
                    prof.step_done()
                dispatcher.report(
                    task.task_id, wid, success=True,
                    records_processed=task.num_records,
                )
                tasks_done += 1
            walls[wid] = time.monotonic() - t0
            # snapshot IN-THREAD, at the same instant the external wall
            # measurement stops — join latency must not read as skew
            snaps[wid] = ledger.snapshot()

        with tracing.adopt(trace_id):
            with tracing.span("goodput", workers=n_workers):
                threads = [
                    threading.Thread(target=run_worker, args=(wid,))
                    for wid in range(n_workers)
                ]
                for t in threads:
                    t.start()
                assert killed_event.wait(timeout=120), "victim never died"
                tracing.event(
                    "goodput.kill_worker", worker_id=killed_wid,
                    task_id=abandoned.get("task_id"),
                )
                # the master's reaction: recover the dead worker's
                # leases (worker_died wasted records) and announce the
                # rescale the survivors pay at their next task boundary
                dispatcher.recover_tasks(killed_wid)
                rescale_event.set()
                # the ghost: the dead worker's delayed report arrives
                # after recovery and is rejected — the stale_report
                # evidence bucket
                ghost_accepted = dispatcher.report(
                    abandoned["task_id"], killed_wid, success=True,
                    records_processed=abandoned["records"],
                )
                for t in threads:
                    t.join(timeout=300)
                assert not any(t.is_alive() for t in threads), \
                    "scenario hung"

        # ---- per-worker self-consistency: categories sum to wall ----
        per_worker = {}
        worst_err_pct = 0.0
        for wid, snap in sorted(snaps.items()):
            measured = walls[wid]
            cat_sum = sum(snap["categories"].values())
            err_pct = (
                100.0 * abs(cat_sum - measured) / measured
                if measured else 0.0
            )
            worst_err_pct = max(worst_err_pct, err_pct)
            per_worker[f"worker{wid}"] = {
                "measured_wall_s": round(measured, 6),
                "ledger_wall_s": snap["wall_s"],
                "category_sum_s": round(cat_sum, 6),
                "attribution_error_pct": round(err_pct, 4),
                "overattributed_s": snap["overattributed_s"],
                "goodput_fraction": snap["goodput_fraction"],
                "categories": snap["categories"],
                "rescale_phases": snap["rescale_phases"],
            }
        out["per_worker"] = per_worker
        out["attribution_worst_error_pct"] = round(worst_err_pct, 4)
        out["attribution_within_1pct"] = bool(worst_err_pct <= 1.0)

        # ---- injected phases land in the right buckets ----
        strag = per_worker[f"worker{straggler_wid}"]["categories"]
        peers = [
            per_worker[f"worker{w}"]["categories"]["train_compute"]
            for w in range(n_workers)
            if w not in (straggler_wid, killed_wid)
        ]
        out["straggler_compute_s"] = strag["train_compute"]
        out["peer_compute_s"] = round(max(peers), 6) if peers else 0.0
        out["straggler_in_compute_bucket"] = bool(
            strag["train_compute"] > (max(peers) if peers else 0.0)
        )
        survivor_rescale = [
            per_worker[f"worker{w}"]["categories"]["rescale"]
            for w in range(n_workers) if w != killed_wid
        ]
        out["rescale_seconds_min_survivor"] = round(
            min(survivor_rescale), 6)
        out["rescale_booked_on_survivors"] = bool(
            min(survivor_rescale) > 0.0)

        # ---- wasted-work bill (dispatcher + journal replay) ----
        wasted = dispatcher.wasted_work()
        out["wasted"] = wasted
        by = wasted["by_reason"]
        out["wasted_from_requeued_lease"] = bool(
            by.get("worker_died", {}).get("records", 0) > 0
        )
        out["ghost_report_rejected"] = bool(
            not ghost_accepted
            and by.get("stale_report", {}).get("events", 0) > 0
        )
        journal.close()
        with open(journal.path, encoding="utf-8") as f:
            replayed = replay_lines(f.readlines()).dispatcher
        out["wasted_journal_consistent"] = bool(
            replayed is not None
            and replayed.wasted_records == wasted["wasted_records"]
            and replayed.wasted_events == wasted["wasted_events"]
            and replayed.records_completed == wasted["records_completed"]
            and replayed.wasted_by_reason == by
        )

        # ---- fleet rollup (the headline): frozen in-thread snapshots
        # through the shared ledger-payload shim, so the fleet fraction
        # cannot drift with post-scenario wall ----
        fleet_gp = goodput_lib.FleetGoodput(
            _ledger_stub_membership(snaps), dispatcher)
        fleet_snap = fleet_gp.update()
        out["fleet"] = fleet_snap.get("fleet")
        out["fleet_goodput_fraction"] = (
            fleet_snap.get("fleet") or {}
        ).get("goodput_fraction", 0.0)
        out["trace_id"] = trace_id

        art_dir = os.environ.get("EDL_BENCH_ARTIFACT_DIR")
        if art_dir:
            os.makedirs(art_dir, exist_ok=True)
            # the ledger JSON (the CI job's headline artifact)
            with open(os.path.join(art_dir, "bench-goodput-ledgers.json"),
                      "w") as f:
                json.dump(
                    {"per_worker": per_worker, "fleet": out["fleet"],
                     "wasted": wasted},
                    f, indent=1, sort_keys=True,
                )
            # the journal (replayable by the incident CLI: its filename
            # keeps the journal.jsonl suffix the walker looks for)
            import shutil

            shutil.copyfile(
                journal.path,
                os.path.join(art_dir, "bench-goodput-journal.jsonl"),
            )
            # a health snapshot carrying the fleet goodput rollup (the
            # incident CLI's worker-seconds source)
            with open(
                os.path.join(art_dir, "bench-goodput.health.json"), "w"
            ) as f:
                json.dump(
                    {"role": "bench-goodput",
                     "goodput": fleet_gp.snapshot(),
                     "cluster": {"workers_reporting": n_workers - 1,
                                 "straggler_count": 0, "skew": 1.0}},
                    f, indent=1, sort_keys=True,
                )
            with open(os.path.join(art_dir, "bench-goodput-trace.jsonl"),
                      "w") as f:
                for rec in tracing.get_tracer().records:
                    f.write(json.dumps(rec) + "\n")
    return out


# autoscale chaos leg (ISSUE 14): knob defaults size the scenario to a
# few seconds on a 1-core box while keeping every phase measurable
AS_WORKERS = int(os.environ.get("EDL_BENCH_AS_WORKERS", "3"))
AS_TASKS = int(os.environ.get("EDL_BENCH_AS_TASKS", "30"))
AS_RECORDS_PER_TASK = int(os.environ.get("EDL_BENCH_AS_RECORDS", "64"))
AS_STEPS_PER_TASK = 4
AS_COMPUTE_S = 0.004
#: the deterministic injected straggle: the `worker.train_step.<id>:delay`
#: fault site fires this on EVERY step of the victim (overridable by
#: exporting a full EDL_FAULTS schedule — the CI job does)
AS_STRAGGLE_MS = float(os.environ.get("EDL_BENCH_AS_STRAGGLE_MS", "40"))


class _SyncWorld:
    """A dynamic step barrier: the synchronous-data-parallel model that
    makes a straggler REAL — every member's step completes when the
    slowest member's does (the allreduce wait), so one injected 40 ms
    delay drags the whole fleet, which is exactly what the autoscaler's
    eviction must recover. Members leave permanently (eviction, queue
    drained); waits are bounded so an idle peer (between leases) stalls
    a step, never wedges it."""

    def __init__(self, members):
        self._cv = threading.Condition()
        self._members = set(members)     # guarded_by: _cv
        self._arrived = set()            # guarded_by: _cv
        self._generation = 0             # guarded_by: _cv

    def join(self, wid):
        with self._cv:
            self._members.add(wid)

    def leave(self, wid):
        """Deregister — permanently (eviction) or while idle between
        leases (an idle peer must not gate the training members' steps;
        it rejoins on its next lease)."""
        with self._cv:
            self._members.discard(wid)
            self._arrived.discard(wid)
            if self._members and self._arrived.issuperset(self._members):
                self._arrived.clear()
                self._generation += 1
            self._cv.notify_all()

    def step(self, wid, timeout=0.3):
        deadline = time.monotonic() + timeout
        with self._cv:
            if wid not in self._members:
                return
            gen = self._generation
            self._arrived.add(wid)
            if self._arrived.issuperset(self._members):
                self._arrived.clear()
                self._generation += 1
                self._cv.notify_all()
                return
            while self._generation == gen and wid in self._members:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    # a peer is off leasing/idle: release this step (the
                    # bound is >> any step time, so this only fires at
                    # the queue's tail)
                    self._arrived.discard(wid)
                    return
                self._cv.wait(remaining)


def _as_scenario(autoscale_on, faults_spec):
    """One twin of the autoscale chaos scenario: a synchronous 3-worker
    fleet over the REAL dispatcher+journal+membership+health stack, with
    the straggler injected through the real fault site. Returns the
    measurement dict; with `autoscale_on` the policy engine (real
    Autoscaler, journaled decisions) evicts the victim; without, the
    straggler drags the fleet to the end — the control twin the goodput
    comparison is made against."""
    import tempfile
    from collections import deque

    from elasticdl_tpu.common import faults
    from elasticdl_tpu.master.autoscaler import Autoscaler, CostModel
    from elasticdl_tpu.master.journal import ControlPlaneJournal
    from elasticdl_tpu.master.membership import Membership
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.observability import goodput as goodput_lib
    from elasticdl_tpu.observability.health import ClusterHealth

    faults.install(faults_spec, seed=7)
    n = max(3, AS_WORKERS)
    straggler_wid = 1
    total_records = AS_TASKS * AS_RECORDS_PER_TASK
    res = {"workers": n, "straggler_worker": straggler_wid}

    tmp_ctx = tempfile.TemporaryDirectory()
    tmp = tmp_ctx.name
    journal = ControlPlaneJournal(tmp)
    dispatcher = TaskDispatcher(
        training_shards=[("train", 0, total_records)],
        records_per_task=AS_RECORDS_PER_TASK,
        num_epochs=1, shuffle=False, task_timeout_s=600.0,
        journal=journal,
    )
    membership = Membership(heartbeat_timeout_s=30.0, journal=journal)
    membership.add_death_callback(dispatcher.recover_tasks)
    # quorum 2 (the satellite): after the eviction the 2-survivor fleet
    # must still be scorable
    health = ClusterHealth(
        membership, min_workers=2, stale_after_s=10.0,
    )
    onsets = []
    health.add_hook(lambda info: onsets.append(
        (time.monotonic(), dict(info))))

    evict_flags = {w: threading.Event() for w in range(n)}
    action_log = []

    class _Target:
        def world_size(self):
            return membership.alive_count()

        def evict(self, worker_id, worker_name=""):
            action_log.append(
                ("evict", worker_id, time.monotonic()))
            evict_flags[worker_id].set()
            return True

        def grow(self):
            action_log.append(("grow", -1, time.monotonic()))
            return True

        def shrink(self):
            action_log.append(("shrink", -1, time.monotonic()))
            return True

    autoscaler = None
    if autoscale_on:
        autoscaler = Autoscaler(
            journal=journal,
            cost_model=CostModel(rescale_cost_s=0.05, horizon_s=10.0),
            min_world=2, cooldown_s=2.0, hold_s=0.15, action_budget=3,
        ).subscribe(health=health)
        autoscaler.bind_target(_Target())

    infos = [membership.register(f"bench-as-w{i}") for i in range(n)]
    wids = [i.worker_id for i in infos]
    world = _SyncWorld(wids)
    walls, snaps, drain = {}, {}, {}

    def run_worker(wid):
        ledger = goodput_lib.GoodputLedger()
        recent = deque(maxlen=16)
        t0 = time.monotonic()
        steps = 0
        try:
            while True:
                task = dispatcher.get(wid)
                if task is None:
                    world.leave(wid)   # idle: don't gate peers' steps
                    if dispatcher.finished():
                        return
                    with ledger.phase("lease_wait"):
                        time.sleep(0.002)
                    continue
                world.join(wid)
                done = 0
                # captured BEFORE any drain report: the dispatcher
                # advances task.start in place when it requeues the
                # remainder, so num_records shrinks under us
                records_total = task.num_records
                per_step = records_total // AS_STEPS_PER_TASK
                for _ in range(AS_STEPS_PER_TASK):
                    if evict_flags[wid].is_set():
                        # the drain handshake, mid-task: report the
                        # applied prefix (retired against the drain
                        # checkpoint in the real worker), requeue the
                        # remainder FRONT retry-free, leave the world
                        dispatcher.report(
                            task.task_id, wid, success=False,
                            preempted=True, records_processed=done,
                        )
                        drain["records_done"] = done
                        drain["remainder"] = records_total - done
                        return
                    own_t0 = time.perf_counter()
                    with ledger.phase("train_compute"):
                        time.sleep(AS_COMPUTE_S)
                    # the injected straggle (worker.train_step.<id>
                    # fault site): deliberately OUTSIDE the compute
                    # attribution — a straggler's excess wall is
                    # non-productive chip time, which is what the
                    # goodput comparison below prices
                    faults.fire(f"worker.train_step.{wid}")
                    own_s = time.perf_counter() - own_t0
                    recent.append(own_s)
                    steps += 1
                    done += per_step
                    # heartbeat telemetry: OWN step time (the scorer's
                    # input), refreshed every step
                    s = sorted(recent)
                    membership.heartbeat(wid, steps, stats={
                        "step_p50_ms": round(
                            1e3 * s[len(s) // 2], 3),
                    })
                    # the allreduce wait: the fleet advances at the
                    # slowest member's pace
                    world.step(wid)
                dispatcher.report(
                    task.task_id, wid, success=True,
                    records_processed=task.num_records,
                )
        finally:
            world.leave(wid)
            walls[wid] = time.monotonic() - t0
            snaps[wid] = ledger.snapshot()

    threads = [
        threading.Thread(target=run_worker, args=(w,)) for w in wids
    ]
    scenario_t0 = time.monotonic()
    for t in threads:
        t.start()
    timeline = []
    evict_done = False
    while any(t.is_alive() for t in threads):
        if time.monotonic() - scenario_t0 > 120:
            raise RuntimeError("autoscale scenario hung")
        dispatcher.poke()
        health.update()
        if autoscaler is not None:
            autoscaler.evaluate()
        if (
            autoscale_on and not evict_done and action_log
            and not threads[straggler_wid].is_alive()
        ):
            # the evicted worker's process exit, as the watch loop
            # would see it: mark dead (requeue-front like a death —
            # a no-op here, the drain already released the lease)
            membership.mark_dead(
                straggler_wid, reason="evicted by autoscale policy")
            evict_done = True
        timeline.append((
            time.monotonic(),
            dispatcher.wasted_work()["records_completed"],
        ))
        time.sleep(0.03)
    for t in threads:
        t.join(timeout=10)

    res["wall_s"] = round(time.monotonic() - scenario_t0, 3)
    res["onsets"] = [
        {"t_s": round(ts - scenario_t0, 3),
         "worker_id": info.get("worker_id")}
        for ts, info in onsets
    ]
    res["actions"] = [
        {"kind": k, "worker_id": w, "t_s": round(ts - scenario_t0, 3)}
        for k, w, ts in action_log
    ]
    res["drain"] = dict(drain)
    res["wasted"] = dispatcher.wasted_work()
    res["timeline"] = [
        (round(ts - scenario_t0, 3), recs) for ts, recs in timeline
    ]
    res["autoscaler"] = (
        autoscaler.snapshot() if autoscaler is not None else None
    )
    # fleet goodput over the frozen in-thread ledger snapshots, through
    # the shim shared with bench_goodput (the fraction must not drift
    # with post-scenario wall)
    fleet_gp = goodput_lib.FleetGoodput(
        _ledger_stub_membership(snaps), dispatcher)
    res["goodput"] = fleet_gp.update()
    res["fleet_goodput_fraction"] = (
        res["goodput"].get("fleet") or {}
    ).get("goodput_fraction", 0.0)
    res["_journal"] = journal
    res["_tmp_ctx"] = tmp_ctx
    res["_tmp"] = tmp
    res["_health_snapshot"] = health.snapshot()
    res["_fleet_gp"] = fleet_gp
    return res


def bench_autoscale(mesh=None, np=None):
    """Closed-loop autoscaler chaos leg (ISSUE 14 acceptance): a
    deterministic `worker.train_step.<id>:delay` straggler in a
    synchronous fleet is sensed by the REAL ClusterHealth scorer, the
    REAL Autoscaler evicts it (drain-first) within the policy window,
    throughput recovers, the drained records incur zero wasted-work
    billing, the no-autoscaler control twin ends with a strictly lower
    fleet goodput fraction, and the decision journal replays identically
    across a simulated mid-decision master kill with the cooldown
    inherited (no double-fire). `mesh`/`np` ignored (uniform leg
    signature; jax-free)."""
    import shutil

    from dataclasses import asdict

    from elasticdl_tpu.common import faults
    from elasticdl_tpu.master.autoscaler import Autoscaler, CostModel
    from elasticdl_tpu.master.journal import ControlPlaneJournal, replay_lines
    from elasticdl_tpu.observability import tracing

    tracing.configure(role="bench-autoscale")
    trace_id = tracing.new_trace_id()

    # the documented chaos contract: EDL_FAULTS drives the straggler; an
    # externally-exported schedule (the CI job sets one) wins, the
    # default injects the deterministic per-step delay on worker 1
    spec = os.environ.get("EDL_FAULTS", "")
    if "worker.train_step" not in spec:
        spec = f"worker.train_step.1:delay@ms={AS_STRAGGLE_MS:g}"
    out = {"faults": spec, "trace_id": trace_id}

    try:
        with tracing.adopt(trace_id):
            with tracing.span("autoscale_scenario", twin="autoscaled"):
                on = _as_scenario(True, spec)
            with tracing.span("autoscale_scenario", twin="control"):
                off = _as_scenario(False, spec)
    finally:
        faults.uninstall()

    straggler_wid = on["straggler_worker"]
    out["workers"] = on["workers"]

    # ---- detection + eviction within the policy window ----
    onset = next(
        (o for o in on["onsets"] if o["worker_id"] == straggler_wid), None)
    evict = next((a for a in on["actions"] if a["kind"] == "evict"), None)
    out["straggler_detected"] = bool(onset)
    out["onset_t_s"] = onset["t_s"] if onset else None
    out["evict_t_s"] = evict["t_s"] if evict else None
    out["evicted_straggler"] = bool(
        evict and evict["worker_id"] == straggler_wid)
    # policy window: hold (0.15s) + a few 30ms polls; 5s is generous on
    # a contended box while still proving closed-loop latency
    out["time_to_evict_s"] = (
        round(evict["t_s"] - onset["t_s"], 3) if onset and evict else None
    )
    out["evicted_within_policy_window"] = bool(
        onset and evict and evict["t_s"] - onset["t_s"] <= 5.0
    )

    # ---- throughput recovers after the eviction ----
    def rate(timeline, t_from, t_to):
        pts = [(t, r) for t, r in timeline if t_from <= t <= t_to]
        if len(pts) < 2 or pts[-1][0] <= pts[0][0]:
            return 0.0
        return (pts[-1][1] - pts[0][1]) / (pts[-1][0] - pts[0][0])

    if evict:
        t_ev = evict["t_s"]
        # the post-evict window ends at the LAST time records actually
        # completed, not at thread-join: the queue can drain well before
        # the scenario's bookkeeping tail, and a plateau would dilute
        # the recovered rate into a false non-recovery
        progress = [t for (t, r), (_, r0) in zip(
            on["timeline"][1:], on["timeline"][:-1]) if r > r0]
        t_end = progress[-1] if progress else on["wall_s"]
        out["rate_during_straggle_records_per_s"] = round(
            rate(on["timeline"], 0.0, t_ev), 1)
        out["rate_after_evict_records_per_s"] = round(
            rate(on["timeline"], t_ev + 0.05, t_end), 1)
        out["throughput_recovers"] = bool(
            out["rate_after_evict_records_per_s"]
            > out["rate_during_straggle_records_per_s"]
        )
    else:
        out["throughput_recovers"] = False

    # ---- the drained records incur zero wasted-work billing ----
    by = on["wasted"]["by_reason"]
    drain = on["drain"]
    out["drain"] = drain
    out["wasted_by_reason"] = by
    out["drained_records_zero_waste"] = bool(
        evict
        # the drain released the lease: no worker_died billing at all
        and "worker_died" not in by
        # only the UNPROCESSED remainder re-leases (billed drain_requeue)
        and by.get("drain_requeue", {}).get("records", 0)
        == drain.get("remainder", -1)
        # every record trained exactly once fleet-wide: the drained
        # prefix retired, the remainder re-ran elsewhere
        and on["wasted"]["records_completed"]
        == AS_TASKS * AS_RECORDS_PER_TASK
    )

    # ---- fleet goodput strictly higher than the no-autoscaler twin ----
    out["fleet_goodput_fraction"] = on["fleet_goodput_fraction"]
    out["goodput_fraction_control"] = off["fleet_goodput_fraction"]
    out["autoscale_goodput_gain"] = round(
        on["fleet_goodput_fraction"] - off["fleet_goodput_fraction"], 6)
    out["goodput_higher_than_control"] = bool(
        on["fleet_goodput_fraction"] > off["fleet_goodput_fraction"])

    # ---- decision journal: replay identity + inherited cooldown ----
    journal = on["_journal"]
    journal.close()
    art_dir = os.environ.get("EDL_BENCH_ARTIFACT_DIR")
    if art_dir:
        os.makedirs(art_dir, exist_ok=True)
        # copied BEFORE the takeover reopen below rotates/compacts it
        shutil.copyfile(
            journal.path,
            os.path.join(art_dir, "bench-autoscale-journal.jsonl"),
        )
    with open(journal.path, encoding="utf-8") as f:
        lines = f.readlines()
    replay_a = replay_lines(lines).autoscale
    replay_b = replay_lines(lines).autoscale
    out["journal_autoscale_records"] = (
        replay_a.records if replay_a else 0)
    out["journal_actions_applied"] = (
        replay_a.actions_applied if replay_a else 0)
    # the mid-decision master kill: a successor opens the same journal
    # (replay + generation bump + rotation) and must inherit the exact
    # decision state — then its restored policy engine, handed the SAME
    # straggler signal again, must suppress on the inherited cooldown
    # instead of double-firing
    successor = ControlPlaneJournal(on["_tmp"])
    snap2 = successor.autoscale_snapshot()
    out["journal_replay_identical"] = bool(
        replay_a is not None and snap2 is not None
        and asdict(replay_a) == asdict(replay_b)
        and snap2.actions_applied == replay_a.actions_applied
        and snap2.last_action_ts == replay_a.last_action_ts
        and snap2.by_kind == replay_a.by_kind
    )
    refires = []

    class _RefireTarget:
        def world_size(self):
            return 3

        def evict(self, worker_id, worker_name=""):
            refires.append(worker_id)
            return True

        def grow(self):
            return True

        def shrink(self):
            return True

    restored = Autoscaler(
        journal=successor,
        cost_model=CostModel(rescale_cost_s=0.05, horizon_s=10.0),
        min_world=2, cooldown_s=3600.0, hold_s=0.0, action_budget=3,
    )
    restored.bind_target(_RefireTarget())
    restored._on_straggler({
        "worker_id": straggler_wid, "worker_name": "ghost",
        "score": 40.0, "step_time_p50_s": 0.044,
        "median_step_time_s": 0.004,
    })
    restored.evaluate()
    restored_snap = restored.snapshot()
    out["cooldown_inherited_no_double_fire"] = bool(
        not refires
        and restored_snap["actions_applied"]
        == (replay_a.actions_applied if replay_a else 0)
        and (restored_snap["last_decision"] or {}).get("suppress_reason")
        == "cooldown"
    )
    out["suppressed_decision_journaled"] = bool(
        restored_snap["decision_records"]
        > (replay_a.records if replay_a else 0)
    )
    successor.close()

    if art_dir:
        with open(os.path.join(art_dir, "bench-autoscale-ledgers.json"),
                  "w") as f:
            json.dump(
                {"autoscaled": {"goodput": on["goodput"],
                                "wasted": on["wasted"]},
                 "control": {"goodput": off["goodput"],
                             "wasted": off["wasted"]}},
                f, indent=1, sort_keys=True, default=repr,
            )
        with open(
            os.path.join(art_dir, "bench-autoscale.health.json"), "w"
        ) as f:
            json.dump(
                {"role": "bench-autoscale",
                 "cluster": on["_health_snapshot"],
                 "autoscale": on["autoscaler"],
                 "goodput": on["_fleet_gp"].snapshot()},
                f, indent=1, sort_keys=True, default=repr,
            )
        with open(os.path.join(art_dir, "bench-autoscale-trace.jsonl"),
                  "w") as f:
            for rec in tracing.get_tracer().records:
                f.write(json.dumps(rec) + "\n")
    # drop the non-JSON handles before the record prints (close the
    # control twin's still-open journal first)
    for twin in (on, off):
        twin["_journal"].close()
        twin["_tmp_ctx"].cleanup()
        for k in list(twin):
            if k.startswith("_"):
                twin.pop(k)
    snap = dict(on["autoscaler"] or {})
    # volatile-at-sample-time booleans must not become baseline-compare
    # structure gates (cooldown_active flips with wall-clock phase)
    snap.pop("cooldown_active", None)
    out["autoscaler"] = snap
    return out


def bench_fleet_soak(mesh=None, np=None):
    """Thousand-worker fleet soak (ISSUE 16): protocol-faithful scripted
    worker lifecycles drive the REAL master stack (journal, membership,
    dispatcher, alerts, autoscaler) over compressed virtual time. Two
    chaos legs at EDL_BENCH_FLEET_WORKERS (default 1000) — correlated
    rack loss and a double master kill — must end with the job finished,
    the journal replaying record-identically, zero acked leases lost and
    the incident CLI strict-clean. A third leg runs the noisy-signal
    scenario twice: damped (EWMA + reversal hold, the shipped defaults)
    versus an undamped twin — the damped run must hold position
    (0 reversals) while the twin oscillates. `mesh`/`np` ignored
    (uniform leg signature; jax-free)."""
    import tempfile

    from elasticdl_tpu.fleetsim import builtin_scenario_path, load_scenario
    from elasticdl_tpu.fleetsim.sim import run_scenario

    workers = int(os.environ.get("EDL_BENCH_FLEET_WORKERS", "1000"))
    art_dir = os.environ.get("EDL_BENCH_ARTIFACT_DIR")

    def _one(name, label, overrides=None):
        sc = load_scenario(builtin_scenario_path(name))
        if overrides:
            sc = sc.override(**overrides)
        adir = (os.path.join(art_dir, f"fleet-soak-{label}")
                if art_dir else None)
        with tempfile.TemporaryDirectory(prefix=f"fleetsoak-{label}-") \
                as td:
            if adir is None:
                # always run the incident --strict pass, even when CI
                # isn't keeping the artifacts
                adir = os.path.join(td, "artifacts")
            t0 = time.perf_counter()
            r = run_scenario(sc, os.path.join(td, "journal"),
                             artifacts_dir=adir)
            r["bench_wall_s"] = round(time.perf_counter() - t0, 2)
        return r

    out = {"workers": workers}
    chaos = {}
    for name in ("rack_failure", "master_failover"):
        r = _one(name, name, {"workers": workers})
        chaos[name] = {
            "leases_per_s": r["leases_per_s"],
            "wall_s": r["bench_wall_s"],
            "time_compression": r["time_compression"],
            "job_finished": bool(r["job_finished"]),
            "replay_identical": bool(r["replay"]["identical"]),
            "zero_lost_acked_leases": r["lost_acked_leases"] == 0,
            "incident_strict_clean": r.get("incident_strict_rc") == 0,
            "master_restarts": r["master_restarts"],
            "journal_flush_p99_ms": r["journal"]["flush_probe_p99_ms"],
            "commit_queue_high_water":
                r["journal"]["commit_queue_high_water"],
            # dotted path ends ".<phase>", so the *_p99_ms gate glob
            # deliberately does NOT match these (phase walls are sub-ms
            # and swing with box contention — informational only)
            "poll_phase_p99": {k: v["p99_ms"]
                               for k, v in r["poll_phases"].items()},
        }
    out["scenarios"] = chaos
    # headline: lease throughput the control plane sustained at fleet
    # scale (virtual-time-structured — scripted think time dominates
    # scheduler noise, so the rate is stable across boxes)
    out["leases_per_s_at_1k"] = max(
        c["leases_per_s"] for c in chaos.values())

    damped = _one("noisy_signal", "noisy-damped")
    undamped = _one(
        "noisy_signal", "noisy-undamped",
        {"autoscale": {"damping": 0.0, "reversal_hold_s": 0.0}})
    # the twin's reversal count is SUPPOSED to be large — its field
    # names dodge the *autoscale_reversals gate glob on purpose
    out["noisy_signal"] = {
        "autoscale_reversals": float(damped["autoscale"]["reversals"]),
        "actions_total": sum(
            damped["autoscale"]["actions_by_kind"].values()),
        "replay_identical": bool(damped["replay"]["identical"]),
        "incident_strict_clean": damped.get("incident_strict_rc") == 0,
        "undamped_twin": {
            "reversals_observed": undamped["autoscale"]["reversals"],
            "actions_observed": sum(
                undamped["autoscale"]["actions_by_kind"].values()),
        },
        "damping_beats_undamped": bool(
            undamped["autoscale"]["reversals"]
            > damped["autoscale"]["reversals"]),
    }
    return out


# ---------------------------------------------------------------------- #
# baseline compare mode (ISSUE 11): diff a run's headline numbers against
# a prior artifact, exit nonzero past a regression threshold — the perf
# trajectory machine-checked instead of eyeballed across round logs.

#: (dotted-path glob, direction, absolute slack) — the numeric leaves the
#: comparator gates on. Anything numeric NOT matched here is reported
#: informationally only (absolute wall-clock numbers vary across boxes;
#: ratios, rates and structural metrics are the machine-checkable
#: trajectory). The absolute slack handles near-zero baselines, where a
#: pure percentage threshold is meaningless (overhead_pct hovers around
#: 0 inside box noise: -0.3% -> +1% is not a 400% regression).
_COMPARE_METRICS = (
    ("value", "higher", 0.0),                    # headline samples/s/chip
    ("*rows_per_sec", "higher", 0.0),
    ("*samples_per_sec", "higher", 0.0),
    ("*sharded_speedup", "higher", 0.0),
    ("*flash_speedup", "higher", 0.0),
    ("*leases_per_sec", "higher", 0.0),
    ("*reports_per_sec", "higher", 0.0),
    ("*beats_per_sec", "higher", 0.0),
    ("*recompile_hit_rate", "higher", 0.0),
    ("*recovery_speedup", "higher", 0.0),   # warm/cold RATIO, not a clock
    ("*hot_id_share", "higher", 0.05),
    # NOTE: recovery_s / time_to_recovery_s are deliberately NOT gated —
    # they are sub-second absolute wall clocks that swing with scheduler
    # noise across box classes; the warm/cold ratio above and the
    # structural booleans are the machine-checkable recovery trajectory
    ("*overhead_pct", "lower", 5.0),   # percentage points of box noise
    # latency percentiles carry ms-scale absolute slack: sub-10ms
    # percentiles on a contended box swing 2x run-to-run, and a 4ms ->
    # 9ms journal-commit "regression" is scheduler noise, not a finding
    ("*_p50_ms", "lower", 2.0),
    ("*_p99_ms", "lower", 10.0),
    ("*mfu_pct", "higher", 0.0),
    # ISSUE 12: the fleet goodput fraction is sleep-structured (the
    # scenario's phase durations dominate scheduler noise) but a
    # contended box inflates the overhead residual — 0.1 absolute slack
    ("*fleet_goodput_fraction", "higher", 0.1),
    # ISSUE 13 read-path headlines: the hit rate is distribution-
    # structured (zipf stream), the speedup/blocked ratios are wire-
    # sleep-structured — all stable across boxes; 0.1 absolute slack
    # absorbs contended-runner jitter on the ratio tails
    ("*cache_hit_rate", "higher", 0.1),
    ("*read_speedup_all_layers", "higher", 0.5),
    ("*pull_blocked_vs_off", "lower", 0.05),
    # data_plane (ISSUE 15): reads must stay served (not blocked)
    # through a partition, and the hedged tail must stay bounded —
    # generous absolute slack because both ride loopback RPC noise
    ("*degraded_read_share", "higher", 0.25),
    ("*read_p99_under_partition_ms", "lower", 15.0),
    # wire-speed data plane (ISSUE 18): the sustained per-owner read
    # rate must not regress, and the measured per-call wire cost on
    # the short-circuit lane must stay low — 100 us absolute slack
    # because a contended runner's sleep() floor dominates the ring's
    # own cost at this scale
    ("*rows_per_s_per_owner", "higher", 0.0),
    ("*wire_per_call_us", "lower", 100.0),
    # absolute slack = the scenario's own 1% gate: a contended runner
    # inside the documented invariant must not fail the compare step
    ("*attribution_worst_error_pct", "lower", 1.0),
    # ISSUE 20: the layout controller's flip recovery is measured in
    # VIRTUAL seconds (the controller runs on a virtual clock and the
    # alert windows are fixed fractions of it), so it is structural —
    # the slack absorbs one cooldown's worth of decision-timing drift.
    # The trail imbalance is distribution-structured (fixed-seed zipf).
    ("*layout_recovery_s", "lower", 10.0),
    ("*post_flip_imbalance", "lower", 0.4),
    # ISSUE 19: the diary tail must stay EXPLAINED — the attributed
    # (non-`other`) fraction of the partition tail's slow wall. 0.1
    # absolute slack: the `other` residual is scheduler-noise shaped
    # on a contended box
    ("*p99_attribution_known_share", "higher", 0.1),
    # ISSUE 14: the autoscaled-vs-control goodput gap is sleep-
    # structured (the injected straggle dominates scheduler noise) but
    # both fractions carry a contended-box overhead residual — 0.1
    # absolute slack, same rationale as fleet_goodput_fraction. The
    # time_to_evict_s wall clock is deliberately NOT gated (the
    # evicted_within_policy_window boolean is the structural gate).
    ("*autoscale_goodput_gain", "higher", 0.1),
    # ISSUE 16 fleet soak: the 1k-worker lease rate is virtual-time-
    # structured (scripted think time dominates scheduler noise); the
    # damped noisy-signal run must hold at ZERO reversals — any upward
    # move is an oscillation regression, so no slack. (The undamped
    # twin's count is deliberately named reversals_observed so this
    # glob never gates it.)
    ("*leases_per_s_at_1k", "higher", 0.0),
    ("*autoscale_reversals", "lower", 0.0),
)

#: paths NEVER gated even when a metric glob matches: scenario-record
#: fields whose magnitude documents the experiment rather than the
#: system's quality — the kill-window pull p99 is SUPPOSED to be large
#: (it measures the injected outage), and the alert thresholds derive
#: from the run's own baseline
_COMPARE_EXCLUDE = (
    "*.alert.*",
    # goodput scenario-record fields: per-category absolute seconds and
    # the wasted bill document the EXPERIMENT (sleep choices, task
    # spans), not the system's quality — the booleans and the fraction
    # are the gates
    "*.per_worker.*", "*.wasted.*", "*.fleet.categories.*",
)

#: boolean leaves: True in the baseline must stay True (structure gates —
#: bit-exactness, exactly-once, warm resharding, replay identity)
_COMPARE_BOOLS = True


def _numeric_leaves(doc, prefix=""):
    """Yield (dotted_path, value) for every number/bool leaf."""
    if isinstance(doc, dict):
        for k in sorted(doc):
            yield from _numeric_leaves(doc[k], f"{prefix}.{k}" if prefix
                                       else str(k))
    elif isinstance(doc, bool):
        yield prefix, doc
    elif isinstance(doc, (int, float)):
        yield prefix, float(doc)


def _compare_direction(path):
    import fnmatch

    for pattern in _COMPARE_EXCLUDE:
        if fnmatch.fnmatch(path, pattern):
            return None, 0.0
    for pattern, direction, slack in _COMPARE_METRICS:
        if fnmatch.fnmatch(path, pattern):
            return direction, slack
    return None, 0.0


def bench_compare(baseline_doc, current_doc, threshold_pct=30.0):
    """Diff two bench records. A gated metric regresses when it moves
    the WRONG way by more than threshold_pct; a baseline-True boolean
    going False always regresses; a gated metric MISSING from the
    current record regresses (a silently-dropped leg must not read as
    green). Returns the report dict; `regressions` non-empty = fail."""
    base = dict(_numeric_leaves(baseline_doc))
    cur = dict(_numeric_leaves(current_doc))
    thr = float(threshold_pct) / 100.0
    compared, regressions, info = [], [], []
    for path, b in sorted(base.items()):
        if isinstance(b, bool):
            c = cur.get(path)
            if b is True and c is not True:
                regressions.append({
                    "path": path, "baseline": True, "current": c,
                    "why": "boolean gate went false/missing",
                })
            continue
        direction, slack = _compare_direction(path)
        c = cur.get(path)
        if direction is None:
            if isinstance(c, float):
                info.append({"path": path, "baseline": b, "current": c})
            continue
        if c is None or isinstance(c, bool):
            regressions.append({
                "path": path, "baseline": b, "current": None,
                "why": "gated metric missing from current record",
            })
            continue
        entry = {"path": path, "baseline": b, "current": c,
                 "direction": direction}
        # the allowed move combines the relative threshold with the
        # metric's absolute slack (whichever is more permissive), so
        # near-zero baselines don't turn box noise into "regressions"
        margin = max(abs(b) * thr, slack)
        if direction == "higher":
            bad = c < b - margin
        else:
            bad = c > b + margin
        entry["ratio"] = round(c / b, 4) if b else None
        compared.append(entry)
        if bad:
            regressions.append(dict(entry, why=(
                f"{direction}-is-better metric moved "
                f"{'down' if direction == 'higher' else 'up'} past "
                f"{threshold_pct}%")))
    # gated metrics present ONLY in the current record (a new leg added
    # since the baseline was cut): a NOTE, never a failure — the next
    # baseline refresh adopts them (ISSUE 12 satellite; without this, a
    # freshly-added leg reads as untracked silence)
    new_metrics = []
    for path, c in sorted(cur.items()):
        if path in base or isinstance(c, bool):
            continue
        direction, _ = _compare_direction(path)
        if direction is not None:
            new_metrics.append({
                "path": path, "current": c,
                "note": "new metric, no baseline",
            })
    return {
        "threshold_pct": float(threshold_pct),
        "compared": compared,
        "regressions": regressions,
        "informational": info,
        "new_metrics": new_metrics,
    }


def _compare_cli(argv):
    """`python bench.py compare [--baseline] <prior.json> <current.json>
    [--threshold-pct N]` — exit 0 ok / 1 regression / 2 usage."""
    args = list(argv)
    threshold = float(os.environ.get("EDL_BENCH_REGRESSION_PCT", "30"))
    if "--threshold-pct" in args:
        i = args.index("--threshold-pct")
        try:
            threshold = float(args[i + 1])
        except (IndexError, ValueError):
            print("--threshold-pct needs a number", file=sys.stderr)
            return 2
        del args[i:i + 2]
    if "--baseline" in args:
        args.remove("--baseline")
    if len(args) != 2:
        print("usage: python bench.py compare [--baseline] <prior.json> "
              "<current.json> [--threshold-pct N]", file=sys.stderr)
        return 2
    docs = []
    for path in args:
        try:
            with open(path, encoding="utf-8") as f:
                docs.append(json.load(f))
        except (OSError, ValueError) as e:
            print(f"unreadable bench record {path}: {e}", file=sys.stderr)
            return 2
    report = bench_compare(docs[0], docs[1], threshold_pct=threshold)
    print(json.dumps(report, indent=1))
    for n in report["new_metrics"]:
        print(
            f"[bench] NOTE {n['path']}: {n['current']} "
            f"({n['note']})", file=sys.stderr,
        )
    for r in report["regressions"]:
        print(
            f"[bench] REGRESSION {r['path']}: {r['baseline']} -> "
            f"{r['current']} ({r['why']})", file=sys.stderr,
        )
    return 1 if report["regressions"] else 0


def _maybe_compare_exit(record):
    """Single-leg `--baseline <prior.json>` mode: after printing the
    fresh record, diff it against the prior artifact and exit nonzero on
    regression (what the bench-* CI jobs wire)."""
    if "--baseline" not in sys.argv:
        return
    i = sys.argv.index("--baseline")
    if i + 1 >= len(sys.argv):
        raise SystemExit("--baseline needs a path")
    path = sys.argv[i + 1]
    threshold = float(os.environ.get("EDL_BENCH_REGRESSION_PCT", "30"))
    try:
        with open(path, encoding="utf-8") as f:
            baseline = json.load(f)
    except (OSError, ValueError) as e:
        raise SystemExit(f"unreadable baseline {path}: {e}")
    report = bench_compare(baseline, record, threshold_pct=threshold)
    for r in report["regressions"]:
        print(
            f"[bench] REGRESSION {r['path']}: {r['baseline']} -> "
            f"{r['current']} ({r['why']})", file=sys.stderr,
        )
    if report["regressions"]:
        raise SystemExit(1)
    print(
        f"[bench] baseline compare ok: {len(report['compared'])} gated "
        f"metric(s) within {threshold}% of {path}", file=sys.stderr,
    )


def _run_leg(leg, mesh, np):
    """One sweep leg (also the `--leg <name>` subprocess entry)."""
    if leg == "headline_pipeline":
        import jax

        n_chips = len(jax.devices())
        headline, mfu = bench_deepfm(mesh, np)
        pipeline_sps, host_sps = bench_pipeline(mesh, np)
        return {
            "value": round(headline / n_chips, 1),
            "pipeline_samples_per_sec": round(pipeline_sps, 1),
            "pipeline_host_samples_per_sec": round(host_sps, 1),
            "n_chips": n_chips,
            **mfu,
        }
    if leg == "mnist_cnn":
        return bench_config(
            mesh, np, "mnist.mnist_cnn", 1024,
            _image_batches((28, 28, 1), 10),
        )
    if leg == "cifar10_resnet20":
        return bench_config(
            mesh, np, "cifar10.resnet", 512,
            _image_batches((32, 32, 3), 10),
        )
    if leg == "resnet50_imagenet":
        return bench_config(
            mesh, np, "resnet50.resnet50", 32,
            _image_batches((224, 224, 3), 1000),
            model_params={"image_size": 224},
        )
    if leg == "census_wide_deep":
        return bench_config(mesh, np, "census.wide_deep", 4096,
                            _census_batches)
    if leg == "xdeepfm":
        # parity config #4b: DeepFM + CIN tower, same Criteo batch shape
        def criteo_batches(np, batch):
            out = []
            for i in range(4):
                r = np.random.RandomState(200 + i)
                out.append({
                    "features": {
                        "dense": r.rand(batch, 13).astype(np.float32),
                        "cat": r.randint(0, 1 << 30, (batch, 26)).astype(
                            np.int32),
                    },
                    "labels": r.randint(0, 2, (batch,)).astype(np.int32),
                })
            return out

        return bench_config(
            mesh, np, "deepfm.xdeepfm", 4096, criteo_batches,
            model_params={"field_vocab": FIELD_VOCAB},
        )
    if leg == "time_to_auc":
        return bench_time_to_auc(mesh, np)
    if leg == "rescale":
        return bench_rescale(mesh, np)
    if leg == "control_plane":
        return bench_control_plane(mesh, np)
    if leg == "goodput":
        return bench_goodput(mesh, np)
    if leg == "autoscale":
        return bench_autoscale(mesh, np)
    if leg == "fleet_soak":
        return bench_fleet_soak(mesh, np)
    if leg == "embedding_tier":
        return bench_embedding_tier(mesh, np)
    if leg == "data_plane":
        return bench_data_plane(mesh, np)
    if leg == "obs_overhead":
        return bench_observability_overhead(mesh, np)
    if leg == "transformer_lm":
        # the Pallas flash-attention kernel vs the XLA materialized-scores
        # path, same model/batch (ops/pallas_attention.py; TPU only — on CPU
        # both runs take the XLA path and the "speedup" reads ~1.0)
        def lm_batches(np, batch):
            out = []
            for i in range(4):
                r = np.random.RandomState(i)
                toks = r.randint(0, 8192, (batch, 1024)).astype(np.int32)
                out.append({"features": toks, "labels": toks})
            return out

        params = {"vocab": 8192, "num_layers": 4, "dim": 512, "heads": 8,
                  "max_len": 1024}
        prev = os.environ.get("EDL_FLASH")
        try:
            os.environ["EDL_FLASH"] = "0"
            xla = bench_config(mesh, np, "transformer.transformer_lm", 8,
                               lm_batches, model_params=params)
        finally:
            os.environ.pop("EDL_FLASH", None)
            if prev is not None:
                os.environ["EDL_FLASH"] = prev
        flash = bench_config(mesh, np, "transformer.transformer_lm", 8,
                             lm_batches, model_params=params)
        return {
            "flash": flash, "xla_attention": xla,
            "flash_speedup": round(xla["step_ms"] / flash["step_ms"], 2),
        }
    raise SystemExit(f"unknown leg {leg!r}")


# Ordered by evidence priority, not logical grouping: the global deadline
# skips TRAILING legs when budget runs dry, so the legs that have never
# appeared in a valid BENCH record (embedding scatter fix, flash speedup,
# the time-to-AUC north-star miniature) run first, and resnet50 — the
# longest staging+compile — runs last so its overrun can't void the others.
SWEEP_LEGS = (
    "rescale", "control_plane", "goodput", "autoscale", "fleet_soak",
    "embedding_tier",
    "data_plane", "obs_overhead", "transformer_lm",
    "time_to_auc", "mnist_cnn", "census_wide_deep", "xdeepfm",
    "cifar10_resnet20", "resnet50_imagenet",
)
LEG_TIMEOUT_S = int(os.environ.get("EDL_BENCH_LEG_TIMEOUT_S", "420"))
# import time ~= leg-subprocess start: lets long-running legs budget
# against their OWN kill deadline (see bench_time_to_auc)
_PROC_T0 = time.perf_counter()
# GLOBAL wall-clock budget, measured from process start and covering
# EVERYTHING (probe + headline + retries + sweep): once the deadline nears,
# remaining legs are skipped (recorded as such) and the JSON line prints.
# Round 3 lesson: the old budget only capped the sweep, so two 600 s hung
# headline attempts pushed past the driver's own timeout and the round lost
# its BENCH record entirely. The default keeps the worst case (last leg
# launched just under the deadline minus its clamped timeout, plus the 20 s
# print reserve) comfortably below the driver's kill.
BUDGET_S = int(os.environ.get("EDL_BENCH_BUDGET_S", "1100"))
# Device probe: `jax.devices()` in a throwaway subprocess, so this parent
# never holds the chip its leg subprocesses need.
PROBE_TIMEOUT_S = int(os.environ.get("EDL_BENCH_PROBE_TIMEOUT_S", "75"))


def _remaining_s():
    return BUDGET_S - (time.perf_counter() - _PROC_T0)


def _probe_devices():
    """(n_devices, platform) via a subprocess jax.devices(), or an error
    string if the probe dies/hangs — without this process touching a
    backend."""
    import subprocess

    try:
        snippet = (
            "import os, jax, json\n"
            "if os.environ.get('EDL_BENCH_CPU') == '1':\n"
            "    jax.config.update('jax_platforms', 'cpu')\n"
            "ds = jax.devices()\n"
            "print(json.dumps({'n': len(ds), 'platform': ds[0].platform}))\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", snippet],
            capture_output=True, timeout=PROBE_TIMEOUT_S,
        )
    except subprocess.TimeoutExpired:
        return None, (
            f"device probe failed: jax.devices() did not answer within "
            f"{PROBE_TIMEOUT_S}s"
        )
    try:
        info = json.loads(proc.stdout.decode().strip().splitlines()[-1])
        return (info["n"], info["platform"]), None
    except Exception as e:
        # probe crashed / printed garbage: an environment or code bug —
        # say so, with the child's stderr
        tail = proc.stderr.decode(errors="replace").strip()[-300:]
        return None, (
            f"device probe crashed ({type(e).__name__}, child rc="
            f"{proc.returncode}): {tail}"
        )


def main():
    if len(sys.argv) >= 2 and sys.argv[1] == "compare":
        # `python bench.py compare <prior.json> <current.json>`: diff two
        # bench records, exit 1 past the regression threshold (jax-free —
        # CI's machine check on the perf trajectory)
        raise SystemExit(_compare_cli(sys.argv[2:]))

    if len(sys.argv) >= 2 and sys.argv[1] == "control_plane":
        # `python bench.py control_plane`: the swarm scenario alone, one
        # JSON line — deliberately BEFORE any jax import (no devices are
        # touched; the leg must run on a box with no backend at all)
        record = {"control_plane": bench_control_plane()}
        print(json.dumps(record))
        _maybe_compare_exit(record)
        return

    if len(sys.argv) >= 2 and sys.argv[1] == "goodput":
        # `python bench.py goodput`: the fleet goodput scenario alone
        # (ISSUE 12) — jax-free like control_plane, before any jax import
        record = {"goodput": bench_goodput()}
        print(json.dumps(record))
        _maybe_compare_exit(record)
        return

    if len(sys.argv) >= 2 and sys.argv[1] == "data_plane":
        # `python bench.py data_plane`: the partition-tolerant gRPC
        # data-plane chaos leg alone (ISSUE 15) — jax-free, before any
        # jax import; owners run as real subprocesses over real gRPC.
        # An exported EDL_FAULTS schedule (the chaos-data-plane CI job
        # sets one) replaces the leg's default client-side drop rule.
        record = {"data_plane": bench_data_plane()}
        print(json.dumps(record))
        _maybe_compare_exit(record)
        return

    if len(sys.argv) >= 2 and sys.argv[1] == "autoscale":
        # `python bench.py autoscale`: the closed-loop autoscaler chaos
        # leg alone (ISSUE 14) — jax-free, before any jax import. The
        # injected straggler honors an exported EDL_FAULTS schedule
        # (the chaos-autoscale CI job sets one) and defaults to the
        # deterministic worker.train_step.1 delay.
        record = {"autoscale": bench_autoscale()}
        print(json.dumps(record))
        _maybe_compare_exit(record)
        return

    if len(sys.argv) >= 2 and sys.argv[1] == "fleet_soak":
        # `python bench.py fleet_soak`: the thousand-worker scenario
        # soak alone (ISSUE 16) — jax-free, before any jax import; the
        # whole fleet is scripted in virtual time against the real
        # master stack. EDL_BENCH_FLEET_WORKERS scales the chaos legs
        # (default 1000; the fleet-soak CI job runs 256).
        record = {"fleet_soak": bench_fleet_soak()}
        print(json.dumps(record))
        _maybe_compare_exit(record)
        return

    import subprocess

    import jax
    import numpy as np

    from elasticdl_tpu.parallel.mesh import build_mesh

    if os.environ.get("EDL_BENCH_CPU") == "1":
        # Development switch: run every leg on the CPU backend (numbers
        # are NOT chip numbers). Pinned BEFORE any jax.devices().
        jax.config.update("jax_platforms", "cpu")

    # Persistent XLA compilation cache shared by every leg subprocess
    # (each applies the same rule, common/runtime.py, so they agree on the
    # directory): each leg re-lowers the same programs, and timed_loop
    # regions always run after warmup, so caching only buys wall-clock
    # headroom against the driver's global deadline. The ONE metric that
    # deliberately times compilation — time_to_auc's
    # compile_and_first_group_s — gets a warm/cold marker
    # (EDL_BENCH_CACHE_PREWARMED, below) so records stay comparable.
    # EDL_BENCH_NO_CACHE=1 opts out entirely.
    if os.environ.get("EDL_BENCH_NO_CACHE") != "1":
        from elasticdl_tpu.common.runtime import (
            compilation_cache_dir,
            configure_jax_runtime,
        )

        cache_dir = compilation_cache_dir()
        prewarmed = bool(os.path.isdir(cache_dir) and os.listdir(cache_dir))
        os.environ.setdefault(
            "EDL_BENCH_CACHE_PREWARMED", "1" if prewarmed else "0")
        configure_jax_runtime()

    if len(sys.argv) >= 2 and sys.argv[1] == "rescale":
        # `python bench.py rescale`: the rescale scenario alone, one JSON
        # line (CI uploads it as an artifact; tier-1 smoke asserts on it)
        mesh = build_mesh({"data": len(jax.devices())})
        record = {"rescale": _run_leg("rescale", mesh, np)}
        print(json.dumps(record))
        _maybe_compare_exit(record)
        return

    if len(sys.argv) >= 2 and sys.argv[1] == "embedding_tier":
        # `python bench.py embedding_tier`: the tier scenario alone, one
        # JSON line (CI uploads it + its trace; tier-1 smoke asserts on
        # the record shape). Serving runs host-side; the reshard phase
        # uses device-mode stores on whatever backend is up.
        record = {"embedding_tier": _run_leg("embedding_tier", None, np)}
        print(json.dumps(record))
        _maybe_compare_exit(record)
        return

    if len(sys.argv) >= 2 and sys.argv[1] == "obs_overhead":
        # `python bench.py obs_overhead`: the recorder+profiler overhead
        # gate alone (ISSUE 9 acceptance: <= 2% median step time)
        mesh = build_mesh({"data": len(jax.devices())})
        record = {"obs_overhead": _run_leg("obs_overhead", mesh, np)}
        print(json.dumps(record))
        _maybe_compare_exit(record)
        return

    if len(sys.argv) >= 3 and sys.argv[1] == "--leg":
        # subprocess mode: one leg, one JSON line
        if sys.argv[2] == "host_pipeline":
            # jax-free leg: must not touch jax.devices()
            print(json.dumps(bench_host_pipeline(np)))
            return
        mesh = build_mesh({"data": len(jax.devices())})
        print(json.dumps(_run_leg(sys.argv[2], mesh, np)))
        return

    fast = os.environ.get("EDL_BENCH_FAST") == "1"

    def leg_subprocess(leg, timeout_s, retries=0):
        err = "unknown"
        for attempt in range(retries + 1):
            # clamp every attempt to the global deadline (+ keep a 20 s
            # reserve so the final JSON always prints before any driver kill)
            timeout_s = min(timeout_s, _remaining_s() - 20)
            if timeout_s < 30:
                return {"error": f"skipped: bench budget ({BUDGET_S}s) spent"}
            proc = None
            try:
                proc = subprocess.run(
                    [sys.executable, os.path.abspath(__file__), "--leg", leg],
                    capture_output=True,
                    timeout=timeout_s,
                    # the child budgets open-ended loops (time_to_auc)
                    # against the timeout it will actually be killed at —
                    # which may be clipped below LEG_TIMEOUT_S by BUDGET_S
                    env={**os.environ,
                         "EDL_BENCH_EFFECTIVE_TIMEOUT_S": str(int(timeout_s))},
                )
                line = proc.stdout.decode().strip().splitlines()[-1]
                return json.loads(line)
            except Exception as e:  # timeout, bad output, nonzero exit
                # keep the child's stderr tail: that's where the real cause
                # (OOM, import error, backend init) lives
                detail = ""
                stderr = getattr(e, "stderr", None) or (
                    proc.stderr if proc is not None else b""
                )
                if stderr:
                    detail = " | stderr: " + stderr.decode(
                        errors="replace"
                    ).strip()[-300:]
                err = f"{e}{detail}"
                print(f"[bench] leg {leg} attempt {attempt + 1} failed: {err}",
                      file=sys.stderr, flush=True)
        return {"error": err[:500]}

    baseline = os.environ.get("EDL_BENCH_BASELINE")
    baseline = float(baseline) if baseline else DEFAULT_BASELINE

    # Fail-fast device probe: no device, no benchmark — a chip metric is
    # never reported as 0.0 with exit 0.
    probe, probe_err = _probe_devices()
    if probe is None:
        raise SystemExit(f"[bench] {probe_err}")
    n_dev, platform = probe
    print(f"[bench] device probe ok: {n_dev} x {platform}",
          file=sys.stderr, flush=True)

    # The headline runs in a subprocess too (timeout + one retry) so this
    # parent stays off the chip; a headline that still fails is an error.
    head = leg_subprocess("headline_pipeline", LEG_TIMEOUT_S, retries=1)
    if "error" in head or not head.get("value"):
        raise SystemExit(
            f"[bench] headline leg failed: {head.get('error', head)}")
    result = {
        "metric": "deepfm_train_samples_per_sec_per_chip",
        "value": head.get("value", 0.0),
        "unit": "samples/s/chip",
        "platform": platform,
        "pipeline_samples_per_sec": head.get("pipeline_samples_per_sec", 0.0),
        "pipeline_host_samples_per_sec": head.get(
            "pipeline_host_samples_per_sec", 0.0
        ),
    }
    for extra in ("gflops_per_step", "achieved_tflops_per_chip", "mfu_pct"):
        if extra in head:
            result[extra] = head[extra]
    result["vs_baseline"] = (
        round(result["value"] / baseline, 3) if baseline else 1.0
    )

    if not fast:
        # Each sweep leg runs in its OWN subprocess with a hard timeout: one
        # stuck leg must not take the whole bench down, and the chip is
        # released between legs.
        configs = {}
        for leg in SWEEP_LEGS:
            if _remaining_s() < 90:
                configs[leg] = {
                    "error": f"skipped: bench budget ({BUDGET_S}s) spent"}
                continue
            print(f"[bench] leg {leg}...", file=sys.stderr, flush=True)
            configs[leg] = leg_subprocess(leg, LEG_TIMEOUT_S)
        result["configs"] = configs

    print(json.dumps(result))


if __name__ == "__main__":
    main()
