"""Driver `resident_lm_dense`: a language model WITHOUT routers — a dense
stack, here one that is run several times over shared weights with an exit
after every pass — as its device step alone, the input path bypassed. The run
is `drivers/resident_lm_stateless.py`'s with the routing dropped; what is
model-free in the LM drivers is loaded from them (the token generator, the
batches, the sums of device time by scope and by kernel, the scope map, the
program's own counters, the rehearsal's sizes), and everything model-specific
comes from the configuration's own modules:

- its reference (`reference/<model>.py`): `hyper`, `loss(params, batch, hp) ->
  (total, (terms, the mean exit distribution))`, `adamw_step`, `TOLERANCES`;
- its shape functions (`flops/<model>.py`): `SCOPES` and ONE `shape(
  model_params, batch, seq_len)` dict, which the per-layer readers, `mfu_pct`,
  `step_roofline` and `hbm_peak_gib` read;
- the traffic file names the rehearsal's tiny sizes (`rehearse`).

The check (`DenseStepCheck`): the program's own `check_steps` steps — the
timed path's jitted step, one step a dispatch — against the reference's from
the same seeded state: `loss` and every term the zoo's `loss` returns beside
it, each to a limit of its own (`TOLERANCES["<term>_rel"]`); the mean exit
distribution the program counts itself (`exit/pmf` of `TrainState.extra_vars`)
against the reference's (`exit_pmf_abs`); AdamW's first moment and the
parameters' update of every leaf after the last step (`mu_rel_l2`,
`update_rel_l2`). The program's state is released while the reference runs,
and the seconds of the reference's steps are taken out of `setup_s`: set-up is
the program's.

Counters printed and returned: what the program counts itself
(`loop/layer_applications`, `loop/passes`, `exit/pmf`, `exit/entropy`,
`attn/kv_block_visits` beside its causal twin), the share of the traced step
no scope claims (`unattributed`), and the step's MFU by the shape functions.
"""

from __future__ import annotations

import os
import statistics
import time

import numpy as np

from benchmark import check_lm, common

_stateless = common.load_module("drivers", "resident_lm_stateless")
_share, _lm, _resident = _stateless._share, _stateless._lm, _stateless._resident
kernel_seconds, program_counters = _stateless.kernel_seconds, _stateless.program_counters
_apply_rehearsal = _stateless._apply_rehearsal

KERNEL_PREFIXES = ("flash_attention",)
PMF = ("exit", "pmf")       # where the program keeps its mean exit distribution


class DenseStepCheck:
    """`before(state)` copies the starting point; the caller runs the
    program's steps on `self.batches`, ONE step a dispatch;
    `read_program(state, metrics, pmfs)` brings its results to the host; after
    the caller has released the program's state, `compare()` runs the
    reference and compares."""

    def __init__(self, reference, model_params: dict, batches: list):
        self.ref = reference
        self.hp = reference.hyper(model_params)
        self.batches = batches
        self.params0 = self.got = None

    def before(self, state):
        self.params0 = check_lm._host(state.params)

    def read_program(self, state, metrics, pmfs):
        """metrics: the step metrics of each compared step ({name: (1,)});
        pmfs: the program's own `exit/pmf` after each."""
        mu, _ = check_lm.adam_moments(state.opt_state)
        self.got = {
            "terms": {name: np.concatenate([np.asarray(m[name], np.float64).reshape(-1)
                                            for m in metrics]) for name in metrics[0]},
            "pmf": np.asarray(pmfs, np.float64),
            "mu": check_lm._host(mu), "params": check_lm._host(state.params)}

    def reference_steps(self) -> dict:
        """The reference's own trajectory from `params0`; moments rest on the
        host between steps."""
        import jax
        import jax.numpy as jnp

        ref, hp = self.ref, self.hp
        device = jax.local_devices()[0]
        grad = jax.jit(jax.value_and_grad(lambda p, b: ref.loss(p, b, hp), has_aux=True))
        adamw = jax.jit(lambda p, g, m, v, t: ref.adamw_step(p, g, m, v, t, hp["adamw"]),
                        donate_argnums=(0, 2, 3))
        zeros = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree))
        terms_all, pmfs = [], []
        with jax.default_matmul_precision("highest"), jax.default_device(device):
            params = jax.device_put(self.params0, device)
            mu = nu = None
            for i, batch in enumerate(self.batches):
                ref_batch = {"tokens": jnp.asarray(batch["features"], jnp.int32),
                             "labels": jnp.asarray(batch["labels"], jnp.int32),
                             "mask": jnp.asarray(batch["mask"], jnp.float32)}
                (value, (terms, pmf)), grads = grad(params, ref_batch)
                terms_all.append({"loss": float(value),
                                  **{k: float(v) for k, v in terms.items()}})
                pmfs.append(np.asarray(pmf, np.float64))
                if mu is None:
                    mu, nu = zeros(params), zeros(params)
                else:
                    mu, nu = jax.device_put((mu, nu), device)
                params, mu, nu = adamw(params, grads, mu, nu, jnp.float32(i + 1))
                del grads
                mu, nu = check_lm._host(mu), check_lm._host(nu)
        return {"terms": {k: np.asarray([t[k] for t in terms_all]) for k in terms_all[0]},
                "pmf": np.asarray(pmfs), "mu": mu, "params": check_lm._host(params)}

    def compare(self) -> dict:
        marks = [("start", time.monotonic())]
        want = self.reference_steps()
        marks.append(("reference_steps", time.monotonic()))
        tolerances, figures, failures = self.ref.TOLERANCES, {}, []

        def hold(name, value, limit):
            figures[name] = value
            if not value <= limit:
                failures.append(f"{name} {value:.4g} > {limit:.4g}")

        for name, got in sorted(self.got["terms"].items()):
            ours = want["terms"][name]
            # (one pass has no entropy term: 0 on both sides)
            hold(f"{name}_rel",
                 float(np.max(np.abs(got - ours) / np.maximum(np.abs(ours), 1e-30))),
                 tolerances[f"{name}_rel"])
            figures[f"{name}_program"] = [float(x) for x in got]
            figures[f"{name}_reference"] = [float(x) for x in ours]
            if not np.all(np.isfinite(got)):
                failures.append(f"non-finite {name}")
        hold("exit_pmf_abs", float(np.max(np.abs(self.got["pmf"] - want["pmf"]))),
             tolerances["exit_pmf_abs"])
        figures["exit_pmf_program"] = self.got["pmf"].tolist()
        figures["exit_pmf_reference"] = want["pmf"].tolist()
        for leaf in sorted(self.params0):
            # the update's error is the parameters' (the starting point cancels)
            for kind, ours, theirs, base in (
                    ("mu_rel_l2", self.got["mu"][leaf], want["mu"][leaf], None),
                    ("update_rel_l2", self.got["params"][leaf], want["params"][leaf],
                     self.params0[leaf])):
                table = tolerances[kind]
                hold(f"{kind}.{leaf}", check_lm._rel_l2(ours, theirs, base),
                     table.get(leaf, table["default"]))
        figures["leaves_compared"] = len(self.params0)
        marks.append(("compared", time.monotonic()))
        figures["seconds"] = {b[0]: round(b[1] - a[1], 2) for a, b in zip(marks, marks[1:])}
        return {"ok": not failures, "figures": figures, "failures": failures}


def program_check(trainer, spec, mesh, zoo, reference, model_params, check_batches,
                  fresh_state, say) -> dict:
    """The cell's check: the program's steps on `check_batches`, one step a
    dispatch, read back; its state released; the reference's steps; the
    comparison. Returns `compare()`'s verdict. (The signature is every LM
    driver's: `tests/zoo_lm.py::run_check` calls them alike. `zoo` is what
    the departures patch; nothing here reads it.)"""
    import jax

    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    del zoo
    t = time.monotonic()
    state = fresh_state()
    checker = DenseStepCheck(reference, model_params, check_batches)
    checker.before(state)
    metrics, pmfs = [], []
    for step_batch in check_batches:
        state, m = trainer.train_many(state, shard_batch_stack(
            mesh, [step_batch], spec.batch_partition))
        metrics.append(m)
        # read now: the next step donates the state this lives in
        pmfs.append(jax.device_get(_share._get_path(state.extra_vars, PMF)))
    checker.read_program(state, jax.device_get(metrics), pmfs)
    del state, m            # the reference needs the chip's memory
    say(f"check: the program's {len(check_batches)} steps read back at "
        f"{time.monotonic() - t:.1f} s")
    verdict = checker.compare()
    say(f"check against the reference in {time.monotonic() - t:.1f} s: "
        f"{verdict['figures']}")
    for failure in verdict["failures"]:
        say(f"CHECK FAILED: {failure}")
    return verdict


def run(ctx) -> dict:
    config, traffic = ctx["config"], ctx["traffic"]
    chips, seed, trace = int(ctx["cell"]["chips"]), ctx["seed"], ctx["trace"]
    say = ctx["say"]
    if ctx["rehearse"]:
        _apply_rehearsal(config, traffic)

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
    compiles = _resident.CompileCounter()
    cfg, spec, mesh, trainer = _resident.build_trainer(config, devices, seed)
    cache_dir = configure_jax_runtime(cfg)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    say(f"devices: {platform} {devices[0].device_kind} x{chips}; "
        f"compile cache at {cache_dir}")

    model_params = common.model_params(config)
    reference = common.load_module("reference", common.model_name(config))
    flops = common.load_module("flops", common.model_name(config))
    hp = reference.hyper(model_params)

    # ---- sequences, from the seed ---------------------------------------- #
    batch = int(traffic["batch_per_chip"]) * chips
    seq_len = int(traffic["seq_len"])
    k = int(traffic["steps_per_dispatch"])
    stacks = int(traffic["distinct_stacks"])
    check_steps = int(traffic["check_steps"])
    t = time.monotonic()
    tokens = _lm.tokens_from_seed(seed, stacks * k * batch, seq_len, hp["vocab_size"],
                                  float(traffic["zipf_s"]))
    say(f"generated {tokens.shape[0]} sequences of {seq_len} + 1 tokens in "
        f"{time.monotonic() - t:.1f} s")
    check_batches = _lm._batches(tokens, batch, 0, check_steps)

    def fresh_state():
        state = trainer.init_state(check_batches[0])
        jax.block_until_ready((state.params, state.extra_vars))
        return state

    # ---- correct? -------------------------------------------------------- #
    verdict = program_check(trainer, spec, mesh, None, reference, model_params,
                            check_batches, fresh_state, say)

    # ---- the window's state and stacks, resident -------------------------- #
    t = time.monotonic()
    state = fresh_state()
    resident = [
        shard_batch_stack(mesh, _lm._batches(tokens, batch, s * k, k),
                          spec.batch_partition)
        for s in range(stacks)]
    jax.block_until_ready(resident)
    del tokens
    say(f"state again from the seed and {stacks} stacks of {k} x {batch} x "
        f"{seq_len} on the device in {time.monotonic() - t:.1f} s")
    t = time.monotonic()
    exe = trainer.aot_compile_train_many(state, resident[0])
    hlo_text = exe.as_text()
    scopes = _share.scope_map(hlo_text, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    mem = exe.memory_analysis()
    say(f"window program compiled or loaded in {time.monotonic() - t:.1f} s: "
        f"{len(scopes)} instructions under a named scope; memory_analysis: "
        f"arguments {mem.argument_size_in_bytes}, outputs {mem.output_size_in_bytes}, "
        f"aliased {mem.alias_size_in_bytes}, temporaries {mem.temp_size_in_bytes} bytes")

    losses_finite = True
    last_metrics = {}

    def dispatch(i):
        nonlocal state
        state, metrics = trainer.train_many(state, resident[i % stacks])
        return metrics

    def readback(metrics):
        nonlocal losses_finite, last_metrics
        last_metrics = {name: np.asarray(v) for name, v in metrics.items()}
        losses_finite &= all(bool(np.all(np.isfinite(v))) for v in last_metrics.values())

    t = time.monotonic()
    readback(dispatch(0))                   # warm-up: this shape, no other
    counted_first = program_counters(state)
    say(f"warm-up dispatch in {time.monotonic() - t:.1f} s; the program's own "
        f"counters after its {k} steps: {counted_first}")

    # ---- the window -------------------------------------------------------- #
    misses_before = trainer.compile_stats().get("misses")
    compiles_before = compiles.count
    # set-up is the program's: the reference's own steps are the yardstick's
    reference_s = verdict["figures"]["seconds"]["reference_steps"]
    setup_s = time.monotonic() - ctx["t0"] - reference_s
    say(f"set-up {setup_s:.1f} s, the reference's steps ({reference_s:.1f} s) left out")
    dispatches, ends = 0, []
    t0 = time.perf_counter()
    while True:
        readback(dispatch(dispatches + 1))
        dispatches += 1
        wall = time.perf_counter() - t0
        ends.append(wall)
        if wall >= ctx["seconds"]:
            break
    each = sorted(b - a for a, b in zip([0.0] + ends, ends))
    median_s = statistics.median(each)
    compiled_in_window = (compiles.count - compiles_before) + (
        trainer.compile_stats().get("misses") != misses_before)
    steps = dispatches * k
    say(f"window: {dispatches} dispatches, {steps} steps in {wall:.3f} s "
        f"(a dispatch: least {each[0]:.4f}, median {median_s:.4f}, "
        f"most {each[-1]:.4f} s; {steps * batch / wall / chips:.3f} samples/s/chip "
        f"= {steps * batch * seq_len / wall / chips:.0f} tokens/s/chip over the "
        f"whole wall); {compiled_in_window} compilation(s) inside it")
    loss_terms = {name: [float(x) for x in v.reshape(-1)]
                  for name, v in sorted(last_metrics.items())}
    say(f"the last dispatch's losses, step by step: {loss_terms}")
    counted_last = program_counters(state)
    say(f"the program's own counters after {k + steps} steps: {counted_last}")

    # ---- shape-derived floors ---------------------------------------------- #
    peaks = None if ctx["rehearse"] else common.peaks(devices[0].device_kind)
    shape = flops.shape(model_params, batch // chips, seq_len)
    say(f"shape functions: {shape}")
    if peaks:
        rate = k * batch / median_s / chips
        say(f"MFU {100 * shape['model_flops_per_sample'] * rate / peaks['bf16_flops_per_s']:.2f}% "
            f"({rate * seq_len:.0f} tokens/s/chip by the median dispatch)")

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
        with open(os.path.join(ctx["work_dir"], "window_program.hlo.txt"), "w") as f:
            f.write(hlo_text)
        ctx["keep"](f.name, "window_program.hlo.txt")
        reduced = trace_reduce.reduce_file(path)
        traced = trace_reduce.summary(reduced)
        if traced:
            per_op_s = reduced["devices"][min(reduced["devices"])]["per_op_s"]
            traced["steps"] = n * k
            traced["scope_s"] = _lm.seconds_by_scope(per_op_s, scopes)
            traced["kernel_s"] = kernel_seconds(per_op_s, scopes, KERNEL_PREFIXES)
            traced["unattributed_share"] = (
                traced["scope_s"].get("unattributed", 0.0)
                / max(sum(traced["scope_s"].values()), 1e-12))
            say(f"trace of {n * k} steps reduced: "
                f"{ {a: b for a, b in traced.items() if a not in ('device_ops', 'idle_gaps')} }")
            say(f"unattributed: {100 * traced['unattributed_share']:.2f}% of the "
                f"device time of the traced steps")
        else:
            say("the trace holds no TPU plane: nothing to reduce")

    memory = _resident.device_memory(devices, say)
    say(f"peak memory {memory['memory_peak_bytes'] / 2 ** 30:.2f} GiB")

    # ---- the zoo's own metrics, one evaluation step on the first batch ----- #
    evaluation = None
    if trace:       # one more program to compile: where the run is looked at
        evaluation = trainer.metric_results(trainer.eval_step(
            state, check_batches[0], trainer.new_metric_states()))
        say(f"evaluation of the window's last state on the first batch (the last "
            f"exit's token accuracy, the mean exit distribution, the loss): {evaluation}")

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
        "counters": {"program_first": counted_first, "program_last": counted_last,
                     "loss_terms_last_dispatch": loss_terms,
                     "evaluation": evaluation,
                     "memory_analysis": {
                         "arguments": mem.argument_size_in_bytes,
                         "outputs": mem.output_size_in_bytes,
                         "aliased": mem.alias_size_in_bytes,
                         "temporaries": mem.temp_size_in_bytes}},
    }
