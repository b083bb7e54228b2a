"""Driver `resident`: the device step alone, the input path bypassed.

This process builds the mesh, the zoo model through `ModelSpec` and the
`Trainer` exactly as `Worker._build_trainer` does, initialises the state on
the device from `--seed`, and runs `Trainer.train_many` over stacked batches
that already live on the device, rotating a few distinct stacks, with one
read-back of the losses per dispatch (`bench.py`'s `_run_steps` method: the
read-back depends on all the work dispatched). Closed loop, one client. Every
dispatch is one reading of the rate, from the end of the previous read-back to
the end of its own; the window's rate is the median reading.

Set-up: records from `criteo-skew`, state, the correctness check against the
plain reference (`benchmark/check.py`), the stacks' transfer, one warm-up
dispatch. Then the window. With `--trace 1` a few more dispatches run under
the profiler, with `bench.dispatch` / `bench.readback` annotations.
"""

from __future__ import annotations

import os
import statistics
import time

import numpy as np

from benchmark import common, criteo_skew


def build_trainer(config: dict, devices, seed: int):
    """(cfg, spec, mesh, trainer), as `Worker._build_trainer` /
    `_make_trainer` build them from a job's flags."""
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.parallel.mesh import build_job_mesh
    from elasticdl_tpu.training import compile_cache as cc
    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    argv = ["--model_zoo", os.path.join(common.ROOT, "model_zoo"),
            "--model_def", config["model_def"],
            "--model_params", config["model_params"],
            "--shuffle_seed", str(seed)]
    if config.get("mesh_shape"):
        argv += ["--mesh_shape", config["mesh_shape"]]
    cfg = JobConfig.from_argv(argv)
    spec = ModelSpec.from_config(cfg)
    mesh = build_job_mesh(cfg, list(devices))
    trainer = Trainer(
        spec, mesh, remat=cfg.remat, remat_policy=cfg.remat_policy,
        grad_accum=cfg.grad_accum_steps, seed=seed,
        cache_token=cc.job_cache_token(cfg))
    return cfg, spec, mesh, trainer


class CompileCounter:
    """Counts XLA backend compilations through jax.monitoring: the window
    must see none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


def _batches(records: dict, batch: int, first: int, count: int):
    out = []
    for i in range(first, first + count):
        s = slice(i * batch, (i + 1) * batch)
        out.append({
            "features": {"dense": records["dense"][s], "cat": records["cat"][s]},
            "labels": records["labels"][s],
            "mask": np.ones((batch,), np.float32),
        })
    return out


def device_memory(devices, say) -> dict:
    """Peak bytes on the fullest chip, read after the window. The TPU
    allocator counts the arrays a process holds (`bytes_in_use`: state,
    resident batches, outputs) apart from the scratch a running program
    reserves (`peak_bytes_reserved`: on the v5e it is the
    `temp_size_in_bytes` of the window program's `memory_analysis()`, PR 22).
    The window's peak is what is held plus that scratch; the allocator's own
    peak of held arrays, which the check's copies can set earlier, counts if
    it is larger. The parts are reported apart as well."""
    stats = [d.memory_stats() or {} for d in devices]
    say(f"memory_stats of device 0: {stats[0]}")

    def peak(s):
        return max(int(s.get("peak_bytes_in_use", 0)),
                   int(s.get("bytes_in_use", 0)) + int(s.get("peak_bytes_reserved", 0)))

    fullest = max(stats, key=peak)
    return {
        "memory_peak_bytes": peak(fullest),
        "bytes_in_use": int(fullest.get("bytes_in_use", 0)),
        "peak_bytes_in_use": int(fullest.get("peak_bytes_in_use", 0)),
        "peak_bytes_reserved": int(fullest.get("peak_bytes_reserved", 0)),
    }


def run(ctx) -> dict:
    config, traffic = ctx["config"], ctx["traffic"]
    chips, seed, trace = int(ctx["cell"]["chips"]), ctx["seed"], ctx["trace"]
    say = ctx["say"]

    import jax

    from elasticdl_tpu.common.runtime import configure_jax_runtime
    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    devices = jax.devices()
    platform = devices[0].platform
    if not ctx["rehearse"] and platform != "tpu":
        raise SystemExit(f"no accelerator: JAX reports platform {platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chip(s), JAX sees {len(devices)}")
    devices = devices[:chips]
    compiles = CompileCounter()
    cfg, spec, mesh, trainer = build_trainer(config, devices, seed)
    cache_dir = configure_jax_runtime(cfg)
    # this process's small helper programs (row gathers, the reference) are
    # worth caching too: every run is a new process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    say(f"devices: {platform} {devices[0].device_kind} x{chips}; "
        f"compile cache at {cache_dir}")

    # ---- records, from the seed ---------------------------------------- #
    batch = int(traffic["batch_per_chip"]) * chips
    k = int(traffic["steps_per_dispatch"])
    stacks = int(traffic["distinct_stacks"])
    check_steps = int(traffic["check_steps"])
    t = time.monotonic()
    cardinalities = common.load_json(
        "cardinalities", config["cardinalities"] + ".json")["fields"]
    records = criteo_skew.from_traffic(
        seed, stacks * k * batch, cardinalities, traffic)
    say(f"generated {stacks * k * batch} records in {time.monotonic() - t:.1f} s")

    # ---- state on the device, from the seed ----------------------------- #
    t = time.monotonic()
    check_batches = _batches(records, batch, 0, check_steps)
    state = trainer.init_state(check_batches[0])
    jax.block_until_ready(state.params)
    say(f"state initialised in {time.monotonic() - t:.1f} s")

    # ---- correct? ------------------------------------------------------- #
    from benchmark import check as check_lib

    t = time.monotonic()
    model_params = common.model_params(config)
    reference = common.load_module("reference", common.model_name(config))
    checker = check_lib.StepCheck(reference, model_params, check_batches, seed)
    checker.before(state)
    say(f"check: reference's starting point copied at {time.monotonic() - t:.1f} s")
    state, m = trainer.train_many(state, shard_batch_stack(
        mesh, check_batches, spec.batch_partition))
    verdict = checker.after(state, m["loss"])
    say(f"check against the reference in {time.monotonic() - t:.1f} s: "
        f"{verdict['figures']}")
    for failure in verdict["failures"]:
        say(f"CHECK FAILED: {failure}")
    del checker

    # ---- the stacks, resident ------------------------------------------- #
    t = time.monotonic()
    resident = [
        shard_batch_stack(mesh, _batches(records, batch, s * k, k),
                          spec.batch_partition)
        for s in range(stacks)]
    jax.block_until_ready(resident)
    del records
    say(f"{stacks} stacks of {k} x {batch} on the device in "
        f"{time.monotonic() - t:.1f} s")

    losses_finite = True

    def dispatch(i):
        nonlocal state
        state, metrics = trainer.train_many(state, resident[i % stacks])
        return metrics

    def readback(metrics):
        nonlocal losses_finite
        losses_finite &= bool(np.all(np.isfinite(np.asarray(metrics["loss"]))))

    t = time.monotonic()
    readback(dispatch(0))                   # warm-up: this shape, no other
    say(f"warm-up dispatch in {time.monotonic() - t:.1f} s")

    # ---- the window ------------------------------------------------------ #
    misses_before = trainer.compile_stats().get("misses")
    compiles_before = compiles.count
    setup_s = time.monotonic() - ctx["t0"]
    dispatches, ends = 0, []
    t0 = time.perf_counter()
    while True:
        readback(dispatch(dispatches + 1))
        dispatches += 1
        wall = time.perf_counter() - t0
        ends.append(wall)
        if wall >= ctx["seconds"]:
            break
    # One reading per dispatch, and the median of them: a dispatch that a
    # neighbour on the shared host stalls (2.34 s among 1.997 s ones, PR 22)
    # then costs one reading and not 1% of the window.
    each = sorted(b - a for a, b in zip([0.0] + ends, ends))
    median_s = statistics.median(each)
    compiled_in_window = (compiles.count - compiles_before) + (
        trainer.compile_stats().get("misses") != misses_before)
    steps = dispatches * k
    say(f"window: {dispatches} dispatches, {steps} steps in {wall:.3f} s "
        f"(a dispatch: least {each[0]:.4f}, median {median_s:.4f}, "
        f"most {each[-1]:.4f} s; {steps * batch / wall / chips:.1f} samples/s/chip "
        f"over the whole wall); {compiled_in_window} compilation(s) inside it")

    # ---- shape-derived floors -------------------------------------------- #
    flops = common.load_module("flops", common.model_name(config))
    table_rows = int(np.prod(check_lib.get_path(state.params, reference.TABLE).shape[:1]))
    peaks = None if ctx["rehearse"] else common.peaks(devices[0].device_kind)
    shape = {
        "model_flops_per_sample": flops.model_flops_per_sample(model_params),
        "step_bytes_per_chip": flops.step_bytes(model_params, batch) / chips,
        "dense_sweep_bytes_per_chip":
            flops.dense_sweep_bytes(model_params, table_rows) / chips,
        "placement_bytes_per_chip":
            flops.placement_bytes(model_params, batch, table_rows // chips),
        "table_rows": table_rows,
    }
    say(f"shape functions: {shape}")

    traced = None
    if trace:
        from benchmark import trace_reduce

        trace_dir = os.path.join(ctx["work_dir"], "trace")
        n = int(traffic["trace_dispatches"])
        jax.profiler.start_trace(trace_dir)
        try:
            for i in range(n):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    metrics = dispatch(dispatches + 1 + i)
                with jax.profiler.TraceAnnotation("bench.readback"):
                    readback(metrics)
        finally:
            jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(trace_dir)
        ctx["keep"](path, "trace.xplane.pb")
        traced = trace_reduce.summary(trace_reduce.reduce_file(path))
        if traced:
            traced["steps"] = n * k
            say(f"trace of {n * k} steps reduced: "
                f"{ {a: b for a, b in traced.items() if a not in ('device_ops', 'idle_gaps')} }")
        else:
            say("the trace holds no TPU plane: nothing to reduce")

    memory = device_memory(devices, say)

    return {
        "correct": bool(verdict["ok"] and not compiled_in_window and losses_finite),
        "attempted": steps,
        "failed": 0 if losses_finite else steps,
        "setup_s": setup_s,
        "window": {"wall_s": wall, "steps": steps, "samples": steps * batch,
                   "chips": chips, "batch": batch, "readings": dispatches,
                   "samples_per_s": k * batch / median_s,
                   "step_ms": 1e3 * median_s / k},
        "device": {"platform": platform, "kind": devices[0].device_kind,
                   "count": chips, **memory},
        "trace": traced,
        "shape": shape,
        "peaks": peaks,
        "model_params": model_params,
    }
