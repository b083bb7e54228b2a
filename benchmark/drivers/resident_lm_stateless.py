"""Driver `resident_lm_stateless`: `drivers/resident_lm_model.py`'s method for
a share of a deployment whose routers carry NO state — a softmax router with
an auxiliary loss, no selection bias — so nothing is settled before the check
and no bias is threaded through the reference's steps. What is model-free in
the three LM drivers is loaded from them (the token generator, the batches,
the sums of device time by scope and by kernel, the scope map, the counters
of the held share, `ModelStepCheck`'s comparison of the terms of the loss);
everything model-specific comes from the configuration's own modules:

- the zoo module (`model_def`): `expert_assignments(params, tokens, cfg)`,
  the evaluation metrics;
- its reference (`reference/<model>.py`): `hyper`, `loss_terms(params, batch,
  hp, chosen)`, `routers_on(params, router_inputs, hp)`, `adamw_step`,
  `PASSES`, `TOLERANCES`, `EXPERT_PAIRS_FLOOR`;
- its shape functions (`flops/<model>.py`): `SCOPES`, `RAGGED_DOT_SCOPE` and
  ONE `shape(model_params, batch, seq_len, pairs_held)` dict;
- the traffic file names the rehearsal's tiny sizes (`rehearse`).

The step reports `loss` (what is minimised) and the terms the zoo's `loss`
returns beside it; the auxiliary term, which the trainer adds from what the
model sows, is their difference (`loss_aux`), and the check holds each term to
a limit of its own. New in the trace's reduction: device seconds of the Pallas
kernels by the prefixes of their names under each attention scope
(`kernel_s`: a windowed layer's kernels are `flash_attention_swa_*`, a full
layer's `flash_attention_*`), which the per-layer readers of the two kinds of
attention read. Counters printed and returned beside the held share's: what
the program counts itself in `TrainState.extra_vars` (the grid steps its
attention kernels compute and what a causal grid would, per kind of layer;
the share of the pairs held, per layer). Merging the four LM drivers is
ROADMAP B0's.
"""

from __future__ import annotations

import os
import statistics
import sys
import time

import numpy as np

from benchmark import check_lm, common

_model = common.load_module("drivers", "resident_lm_model")
_share, _lm, _resident = _model._share, _model._lm, _model._resident


class StatelessStepCheck(_model.ModelStepCheck):
    """`ModelStepCheck` without a selection bias: the reference's steps thread
    no router state, and nothing of it is compared."""

    def read_program(self, state, metrics, routings):
        check_lm.LMStepCheck.read_program(
            self, state, np.concatenate([m["loss"] for m in metrics]), routings)
        terms = {name: np.concatenate([np.asarray(m[name], np.float64) for m in metrics])
                 for name in metrics[0] if name != "loss"}
        # what the trainer added to the zoo's own terms: the sown auxiliary loss
        terms["loss_aux"] = self.got["losses"] - sum(terms.values())
        self.got["terms"] = terms

    def reference_steps(self) -> dict:
        import jax
        import jax.numpy as jnp

        ref, hp = self.ref, self.hp
        device = jax.local_devices()[0]

        def total_and_rest(p, b, chosen):
            total, terms, own = ref.loss_terms(p, b, hp, chosen)
            return total, (terms, own)

        grad = jax.jit(jax.value_and_grad(total_and_rest, has_aux=True))
        routers_on = jax.jit(lambda p, x: ref.routers_on(p, x, hp))
        adamw = jax.jit(lambda p, g, m, v, t: ref.adamw_step(p, g, m, v, t, hp["adamw"]),
                        donate_argnums=(0, 2, 3))
        zeros = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree))
        losses, terms_all, routing, same = [], [], [], []
        with jax.default_matmul_precision("highest"), jax.default_device(device):
            params = jax.device_put(self.params0, device)
            mu = nu = None
            for i, batch in enumerate(self.batches):
                idx, weights, router_input = self.got["routings"][i]
                same.append(check_lm.routing_figures(
                    idx, weights, *jax.device_get(routers_on(params, router_input))))
                ref_batch = {"tokens": jnp.asarray(batch["features"], jnp.int32),
                             "labels": jnp.asarray(batch["labels"], jnp.int32),
                             "mask": jnp.asarray(batch["mask"], jnp.float32)}
                chosen = check_lm.chosen_mask(idx, hp["num_experts"])
                (value, (terms, own)), grads = grad(params, ref_batch, chosen)
                losses.append(float(value))
                terms_all.append({k: float(v) for k, v in terms.items()})
                routing.append(check_lm.routing_figures(idx, weights, *jax.device_get(own)))
                del own
                if mu is None:
                    mu, nu = zeros(params), zeros(params)
                else:
                    mu, nu = jax.device_put((mu, nu), device)
                params, mu, nu = adamw(params, grads, mu, nu, jnp.float32(i + 1))
                del grads
                mu, nu = check_lm._host(mu), check_lm._host(nu)
        self.want_terms = {k: np.asarray([t[k] for t in terms_all]) for k in terms_all[0]}
        return {"losses": np.asarray(losses), "mu": mu, "params": check_lm._host(params),
                "routing": routing, "router_same_input": same}

    def compare(self) -> dict:
        marks = [("start", time.monotonic())]
        want = self.reference_steps()
        marks.append(("reference_steps", time.monotonic()))
        tolerances = self.ref.TOLERANCES
        # as `ShareStepCheck.compare`: the worst expert is reported, the
        # experts above the floor of pairs (and the pooled rest) are judged
        unjudged = {kind: {**tolerances[kind], "experts": float("inf")}
                    for kind in ("mu_rel_l2", "update_rel_l2")}
        verdict = check_lm.compare(self.got, want, self.params0,
                                   {**tolerances, **unjudged})
        figures, failures = self.expert_figures(want, tolerances)
        for name, got in sorted(self.got["terms"].items()):
            ours = self.want_terms[name]
            rel = float(np.max(np.abs(got - ours) / np.abs(ours)))
            limit = tolerances[f"{name}_rel"]
            figures[f"{name}_rel"] = rel
            figures[f"{name}_program"] = [float(x) for x in got]
            figures[f"{name}_reference"] = [float(x) for x in ours]
            if not rel <= limit:
                failures.append(f"{name}_rel {rel:.4g} > {limit:.4g}")
        verdict["figures"].update(figures)
        verdict["failures"].extend(failures)
        verdict["ok"] = not verdict["failures"]
        marks.append(("compared", time.monotonic()))
        verdict["figures"]["seconds"] = {
            b[0]: round(b[1] - a[1], 2) for a, b in zip(marks, marks[1:])}
        return verdict


def _assignments(zoo, spec):
    """The program's own routing of a batch, jitted: ONE forward-pass program
    for the check's routings and the counters."""
    import jax

    return jax.jit(lambda params, toks: zoo.expert_assignments(params, toks, spec.model.cfg))


def program_check(trainer, spec, mesh, zoo, reference, model_params, check_batches,
                  fresh_state, say, assignments=None) -> dict:
    """The cell's check: the program's steps on `check_batches`, one step a
    dispatch, read back; its state released; the reference's steps; the
    comparison. Returns `compare()`'s verdict."""
    import jax

    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    assignments = assignments or _assignments(zoo, spec)
    t = time.monotonic()
    state = fresh_state()
    checker = StatelessStepCheck(reference, model_params, check_batches)
    checker.before(state)
    metrics, routings = [], []
    for step_batch in check_batches:        # one step a dispatch: the routing
        routings.append(jax.device_get(     # of each step from its own state
            assignments(state.params, step_batch["features"])))
        state, m = trainer.train_many(state, shard_batch_stack(
            mesh, [step_batch], spec.batch_partition))
        metrics.append(m)
    checker.read_program(state, jax.device_get(metrics), routings)
    del state, m            # the reference needs the chip's memory
    say(f"check: the program's {len(check_batches)} steps read back at "
        f"{time.monotonic() - t:.1f} s")
    verdict = checker.compare()
    say(f"check against the reference in {time.monotonic() - t:.1f} s: "
        f"{verdict['figures']}")
    for failure in verdict["failures"]:
        say(f"CHECK FAILED: {failure}")
    return verdict


def kernel_seconds(per_op_s: dict, scopes: dict, prefixes) -> dict:
    """{scope: {prefix: device seconds}} of the custom calls whose instruction
    name starts with one of `prefixes` (a Pallas kernel's `name=`), longest
    prefix first, under the scope the compiled program's text puts them in."""
    out = {}
    for text, seconds in per_op_s.items():
        name = text.lstrip("%").split(" ", 1)[0]
        prefix = next((p for p in sorted(prefixes, key=len, reverse=True)
                       if name.startswith(p)), None)
        if prefix:
            by_prefix = out.setdefault(scopes.get(name, "unattributed"), {})
            by_prefix[prefix] = by_prefix.get(prefix, 0.0) + seconds
    return out


KERNEL_PREFIXES = ("flash_attention_swa", "flash_attention")


def program_counters(state) -> dict:
    """What the program counts itself, as lists: every variable of
    `TrainState.extra_vars` but the sown losses."""
    import jax

    return {f"{group}/{name}": np.asarray(value).tolist()
            for group, leaves in jax.device_get(state.extra_vars).items()
            if group != "losses" for name, value in leaves.items()}


_apply_rehearsal = _model._apply_rehearsal


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
    zoo = sys.modules[spec.module_name]
    cache_dir = configure_jax_runtime(cfg)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    say(f"devices: {platform} {devices[0].device_kind} x{chips}; "
        f"compile cache at {cache_dir}")

    model_params = common.model_params(config)
    reference = common.load_module("reference", common.model_name(config))
    flops = common.load_module("flops", common.model_name(config))
    hp = reference.hyper(model_params)
    held = (hp["first_expert"], hp["n_routed_experts"])

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

    assignments = _assignments(zoo, spec)

    def fresh_state():
        state = trainer.init_state(check_batches[0])
        jax.block_until_ready((state.params, state.extra_vars))
        return state

    def routing_counters(state, toks) -> dict:
        idx = assignments(state.params, toks)[0]
        return _share.held_load(idx, hp["num_experts"], held)

    # ---- correct? -------------------------------------------------------- #
    verdict = program_check(trainer, spec, mesh, zoo, reference, model_params,
                            check_batches, fresh_state, say, assignments)

    # ---- the window's state and stacks, resident -------------------------- #
    t = time.monotonic()
    state = fresh_state()
    resident = [
        shard_batch_stack(mesh, _lm._batches(tokens, batch, s * k, k),
                          spec.batch_partition)
        for s in range(stacks)]
    jax.block_until_ready(resident)
    first_tokens = tokens[:batch, :-1]
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

    passes_run = lambda: np.asarray(
        _share._get_path(state.extra_vars, reference.PASSES), np.int64)
    t = time.monotonic()
    readback(dispatch(0))                   # warm-up: this shape, no other
    load_first = routing_counters(state, first_tokens)
    passes_before = passes_run()
    counted_first = program_counters(state)
    say(f"warm-up dispatch in {time.monotonic() - t:.1f} s; routing after it: "
        f"{load_first}; the program's own counters after its {k} steps: {counted_first}")

    # ---- the window -------------------------------------------------------- #
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
    load_last = routing_counters(state, first_tokens)
    counted_last = program_counters(state)
    say(f"routing after the window: {load_last}; the program's own counters after "
        f"{k + steps} steps: {counted_last}")
    passes = passes_run() - passes_before
    collapsed = bool(np.any(2 * (passes - steps) > steps))
    say(f"passes of the held dispatch in the window's {steps} steps, by sparse "
        f"layer: {passes.tolist()} ({int(np.sum(np.maximum(passes - steps, 0)))} beyond "
        f"one a step{'; COLLAPSED onto the held experts' if collapsed else ''})")

    # ---- shape-derived floors ---------------------------------------------- #
    peaks = None if ctx["rehearse"] else common.peaks(devices[0].device_kind)
    pairs_held = load_last["pairs_held_share"] * hp["moe_layers"] * seq_len \
        * hp["num_experts_per_tok"]
    shape = flops.shape(model_params, batch // chips, seq_len, pairs_held)
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
            traced["flash_attention_s"] = _lm.seconds_by_kernel(per_op_s, "flash_attention")
            traced["kernel_s"] = kernel_seconds(per_op_s, scopes, KERNEL_PREFIXES)
            say(f"trace of {n * k} steps reduced: "
                f"{ {a: b for a, b in traced.items() if a not in ('device_ops', 'idle_gaps')} }")
        else:
            say("the trace holds no TPU plane: nothing to reduce")

    memory = _resident.device_memory(devices, say)
    say(f"peak memory {memory['memory_peak_bytes'] / 2 ** 30:.2f} GiB")

    # ---- the zoo's own metrics, one evaluation step on the first batch ----- #
    evaluation = None
    if trace:       # one more program to compile: where the run is looked at
        evaluation = trainer.metric_results(trainer.eval_step(
            state, check_batches[0], trainer.new_metric_states()))
        say(f"evaluation of the window's last state on the first batch (the share of "
            f"targets each logit stream's arg-max hits, and the loss): {evaluation}")

    return {
        "correct": bool(verdict["ok"] and not compiled_in_window and losses_finite
                        and not collapsed),
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
        "counters": {"routing_first": load_first, "routing_last": load_last,
                     "program_first": counted_first, "program_last": counted_last,
                     "held_passes_in_window": passes.tolist(),
                     "loss_terms_last_dispatch": loss_terms,
                     "evaluation": evaluation,
                     "routing_agreement": verdict["figures"].get("routing_agreement"),
                     "router_same_input_agreement":
                         verdict["figures"].get("router_same_input_agreement"),
                     "memory_analysis": {
                         "arguments": mem.argument_size_in_bytes,
                         "outputs": mem.output_size_in_bytes,
                         "aliased": mem.alias_size_in_bytes,
                         "temporaries": mem.temp_size_in_bytes}},
    }
