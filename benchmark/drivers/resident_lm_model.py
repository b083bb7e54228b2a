"""Driver `resident_lm_model`: a language model's device step alone, the input
path bypassed, with EVERYTHING model-specific taken from the configuration's
own modules, so that nothing here names a model:

- the zoo module (`model_def`): `expert_assignments`, `updated_bias`, the
  evaluation metrics;
- its reference (`reference/<model>.py`): `hyper`, `loss_terms`, `routers_on`,
  `bias_update`, `adamw_step`, `BIAS`, `PASSES`, `TOLERANCES`,
  `EXPERT_PAIRS_FLOOR`;
- its shape functions (`flops/<model>.py`): `SCOPES`, `RAGGED_DOT_SCOPE` and
  ONE `shape(model_params, batch, seq_len, pairs_held)` dict, which the
  per-layer readers take their floors from;
- the traffic file names the rehearsal's tiny sizes (`rehearse`).

The method is `drivers/resident_lm_share.py`'s (one chip's share of a
deployment whose routers carry state that is no parameter), and what is
model-free there and in `drivers/resident_lm.py` is loaded from them: the
token generator, the batches, the sums of device time by scope and by kernel,
the scope map, `ShareStepCheck`, the counters of the held share. New here: a
routers' selection bias settled before anything is compared or timed
(`settled_bias`: `settle_router_steps` forward passes under the model's own
update rule, from the traffic file), and a
loss that is a sum of terms. The program's step reports each term beside the
sum (`Trainer`: a loss that returns a dict), the reference gives them apart
(`loss_terms`), and the check holds each to a limit of its own
(`TOLERANCES["<term>_rel"]`); after the window of a traced run one evaluation
step gives the zoo's own metrics (a second logit stream's accuracy among them). Pointing
the other two LM traffic files at this driver is ROADMAP B0's merge.
"""

from __future__ import annotations

import os
import statistics
import sys
import time

import numpy as np

from benchmark import check_lm, common

_share = common.load_module("drivers", "resident_lm_share")
_lm, _resident = _share._lm, _share._resident


class ModelStepCheck(_share.ShareStepCheck):
    """`ShareStepCheck` with the terms of the loss apart: the program's from
    its step metrics, the reference's from `loss_terms`, each held to
    `TOLERANCES["<term>_rel"]` at every compared step."""

    def read_program(self, state, metrics, routings, biases):
        """metrics: the step metrics of each compared step ({name: (1,)})."""
        super().read_program(state, np.concatenate([m["loss"] for m in metrics]),
                             routings, biases)
        self.got["terms"] = {
            name: np.concatenate([np.asarray(m[name], np.float64) for m in metrics])
            for name in metrics[0] if name != "loss"}

    def reference_steps(self) -> dict:
        """`ShareStepCheck.reference_steps` on `loss_terms`: the same
        trajectory, and the terms of every step's loss in `want["terms"]`."""
        import jax
        import jax.numpy as jnp

        ref, hp = self.ref, self.hp
        device = jax.local_devices()[0]

        def total_and_rest(p, b, chosen, bias):
            total, terms, own = ref.loss_terms(p, b, hp, chosen, bias)
            return total, (terms, own)

        grad = jax.jit(jax.value_and_grad(total_and_rest, has_aux=True))
        routers_on = jax.jit(lambda p, x, bias: ref.routers_on(p, x, hp, bias))
        adamw = jax.jit(lambda p, g, m, v, t: ref.adamw_step(p, g, m, v, t, hp["adamw"]),
                        donate_argnums=(0, 2, 3))
        zeros = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree))
        losses, terms_all, routing, same = [], [], [], []
        with jax.default_matmul_precision("highest"), jax.default_device(device):
            params = jax.device_put(self.params0, device)
            bias = jnp.asarray(self.got["biases"][0])
            mu = nu = None
            for i, batch in enumerate(self.batches):
                idx, weights, router_input = self.got["routings"][i]
                same.append(check_lm.routing_figures(
                    idx, weights, *jax.device_get(routers_on(params, router_input, bias))))
                ref_batch = {"tokens": jnp.asarray(batch["features"], jnp.int32),
                             "labels": jnp.asarray(batch["labels"], jnp.int32),
                             "mask": jnp.asarray(batch["mask"], jnp.float32)}
                chosen = check_lm.chosen_mask(idx, hp["num_experts"])
                (value, (terms, own)), grads = grad(params, ref_batch, chosen, bias)
                losses.append(float(value))
                terms_all.append({k: float(v) for k, v in terms.items()})
                routing.append(check_lm.routing_figures(idx, weights, *jax.device_get(own)))
                del own
                bias = ref.bias_update(bias, jnp.asarray(chosen))
                if mu is None:
                    mu, nu = zeros(params), zeros(params)
                else:
                    mu, nu = jax.device_put((mu, nu), device)
                params, mu, nu = adamw(params, grads, mu, nu, jnp.float32(i + 1))
                del grads
                mu, nu = check_lm._host(mu), check_lm._host(nu)
        self.want_terms = {k: np.asarray([t[k] for t in terms_all]) for k in terms_all[0]}
        return {"losses": np.asarray(losses), "mu": mu, "params": check_lm._host(params),
                "routing": routing, "router_same_input": same,
                "bias": np.asarray(bias, np.float32)}

    def compare(self) -> dict:
        verdict = super().compare()
        for name, got in sorted(self.got["terms"].items()):
            want = self.want_terms[name]
            rel = float(np.max(np.abs(got - want) / np.abs(want)))
            limit = self.ref.TOLERANCES[f"{name}_rel"]
            verdict["figures"][f"{name}_rel"] = rel
            verdict["figures"][f"{name}_program"] = [float(x) for x in got]
            verdict["figures"][f"{name}_reference"] = [float(x) for x in want]
            if not rel <= limit:
                verdict["failures"].append(f"{name}_rel {rel:.4g} > {limit:.4g}")
        verdict["ok"] = not verdict["failures"]
        return verdict


def _assignments(zoo, spec):
    """The program's own routing of a batch, jitted: ONE forward-pass program
    for the settling, the check's routings and the counters."""
    import jax

    return jax.jit(lambda params, bias, toks: zoo.expert_assignments(
        params, bias, toks, spec.model.cfg))


def program_check(trainer, spec, mesh, zoo, reference, model_params, check_batches,
                  fresh_state, say, assignments=None) -> dict:
    """The cell's check: the program's steps on `check_batches`, one step a
    dispatch, read back; its state released; the reference's steps; the
    comparison. Returns `compare()`'s verdict."""
    import jax

    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    assignments = assignments or _assignments(zoo, spec)
    bias_of = lambda state: _share._get_path(state.extra_vars, reference.BIAS)
    t = time.monotonic()
    state = fresh_state()
    checker = ModelStepCheck(reference, model_params, check_batches)
    checker.before(state)
    metrics, routings, biases = [], [], []
    for step_batch in check_batches:        # one step a dispatch: the routing
        bias = bias_of(state)               # of each step from its own state
        biases.append(jax.device_get(bias))
        routings.append(jax.device_get(
            assignments(state.params, bias, step_batch["features"])))
        state, m = trainer.train_many(state, shard_batch_stack(
            mesh, [step_batch], spec.batch_partition))
        metrics.append(m)
    biases.append(jax.device_get(bias_of(state)))
    checker.read_program(state, jax.device_get(metrics), routings, biases)
    del state, m            # the reference needs the chip's memory
    say(f"check: the program's {len(check_batches)} steps read back at "
        f"{time.monotonic() - t:.1f} s")
    verdict = checker.compare()
    say(f"check against the reference in {time.monotonic() - t:.1f} s: "
        f"{verdict['figures']}")
    for failure in verdict["failures"]:
        say(f"CHECK FAILED: {failure}")
    return verdict


def settled_bias(zoo, spec, reference, state, batches, steps: int, held, say,
                 assignments=None):
    """The routers' selection bias after `steps` forward passes over `batches`
    (rotated), each followed by the model's OWN update of it (`updated_bias`:
    b += speed · sign(mean load − load)), the weights left as the seed made
    them. At seeded weights attention is a running mean of the values, the
    same vector at every late position, so every token's router sees nearly
    the same input and a few experts take nearly every pair (PERF.md §6, PR
    32); the bias evens that out over a few hundred steps of a run, and the
    other tens of thousands run balanced. The cell measures those: check and
    window both start from the bias this returns."""
    import jax

    cfg = spec.model.cfg
    bias = _share._get_path(state.extra_vars, reference.BIAS)
    if not steps:
        return jax.device_get(bias)

    assignments = assignments or _assignments(zoo, spec)
    update = jax.jit(lambda bias, idx: zoo.updated_bias(bias, idx, cfg))
    t = time.monotonic()
    for i in range(steps):
        idx = assignments(state.params, bias, batches[i % len(batches)]["features"])[0]
        bias = update(bias, idx)
        if (i + 1) % 100 == 0 or i + 1 == steps:
            load = _share.held_load(jax.device_get(idx), cfg.num_experts, held)
            say(f"selection bias settled for {i + 1} forward passes "
                f"({time.monotonic() - t:.1f} s): largest {float(abs(bias).max()):.3f}, "
                f"pairs on held experts {load}")
    return jax.device_get(bias)     # on the host: a step donates its state


def _with_path(tree, path, value):
    """A copy of the nested mapping `tree` with `value` at `path`."""
    head, rest = path[0], path[1:]
    return {**tree, head: _with_path(tree[head], rest, value) if rest else value}


def settled_state_maker(trainer, zoo, spec, reference, batches, steps: int, held, say,
                        assignments=None):
    """fresh_state(): the state from the seed with the routers' selection bias
    as `settled_bias` leaves it — settled once, here, then copied into every
    state made (a step donates its state, so each gets a copy of its own)."""
    import jax

    state = trainer.init_state(batches[0])
    settled = settled_bias(zoo, spec, reference, state, batches, steps, held, say,
                           assignments)
    del state

    def fresh_state():
        state = trainer.init_state(batches[0])
        state = state.replace(extra_vars=_with_path(
            state.extra_vars, reference.BIAS, jax.device_put(settled)))
        jax.block_until_ready((state.params, state.extra_vars))
        return state

    return fresh_state


def _apply_rehearsal(config: dict, traffic: dict) -> None:
    tiny = common.load_json("rehearse", traffic["rehearse"] + ".json")
    params = common.model_params(config)
    params.update({k: str(v) for k, v in tiny["model_params"].items()})
    config["model_params"] = common.format_model_params(params)
    traffic.update(tiny["traffic"])


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
    fresh_state = settled_state_maker(
        trainer, zoo, spec, reference, _lm._batches(tokens, batch, 0, stacks * k),
        int(traffic["settle_router_steps"]), held, say, assignments)

    def routing_counters(state, toks) -> dict:
        bias = _share._get_path(state.extra_vars, reference.BIAS)
        idx = assignments(state.params, bias, toks)[0]
        return dict(_share.held_load(idx, hp["num_experts"], held),
                    bias_abs_max=float(np.max(np.abs(np.asarray(bias)))))

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
    say(f"warm-up dispatch in {time.monotonic() - t:.1f} s; routing after it: "
        f"{load_first}")

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
    say(f"routing after the window: {load_last}")
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
