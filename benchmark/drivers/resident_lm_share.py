"""Driver `resident_lm_share`: a language model's device step alone, the input
path bypassed, for a configuration that is ONE CHIP'S SHARE of a deployment —
the router chooses among all experts, the chip holds some — and whose routers
carry state that is no parameter (a selection bias in `TrainState.extra_vars`).

The method is `drivers/resident_lm.py`'s, and what is model-free there is
loaded from it (the token generator, the batches, the sums of device time by
scope and by kernel). What that driver fixes for OLMoE comes from the
configuration's own modules here: the scopes and the scope of the grouped
matmuls from its shape functions (`flops/<model>.py`: `SCOPES`,
`RAGGED_DOT_SCOPE`), the experts the router chooses among and the held range
from its reference's `hyper`, where the bias lives from the reference's
`BIAS`. Merging the two drivers is a benchmark PR's (ROADMAP B0).

Set-up: token sequences from the seed, the state, the correctness check
against the plain reference (`ShareStepCheck`: `benchmark/check_lm.py`'s
comparison with the bias threaded through the reference's steps and the
experts' leaves judged with a floor of pairs), the window's program compiled
ahead of time, the stacks' transfer, one warm-up dispatch. Then the window.
With `--trace 1` a few more dispatches run under the profiler and device
time is summed by `jax.named_scope`.

Counters printed and returned, before and after the window: pairs per held
expert (max / mean, empty), pairs held of all pairs, the selection bias's
largest magnitude; the passes the held dispatch ran inside the window, per
sparse-expert layer, from the program's own count (the reference's `PASSES`):
one a step while the pairs on held experts fit a pass — a layer that took
more on over half of the window's steps has collapsed onto the held experts,
the rate is then that of another path, and the run reads `correct: false`;
the check's routing agreement; `memory_analysis()` of the window's program;
peak memory in use and reserved.
"""

from __future__ import annotations

import os
import re
import statistics
import sys
import time

import numpy as np

from benchmark import check_lm, common

_lm = common.load_module("drivers", "resident_lm")
_resident = common.load_module("drivers", "resident")

# jax.checkpoint and a loop inside a scope (the held dispatch's passes) put
# their own names into an instruction's path
_NOT_A_SCOPE = re.compile(
    r"(checkpoint|rematted_computation|remat|closed_call|while/body|while/cond)/")


def scope_of(op_name: str, scopes):
    """The scope an instruction's `op_name` metadata puts it in, or None.
    Backward and recomputed instructions carry the same path inside
    `transpose(jvp(...))` and under `checkpoint/`."""
    path = op_name.replace("transpose(", "").replace("jvp(", "").replace(")", "")
    path = _NOT_A_SCOPE.sub("", path)
    for scope in scopes:
        if re.search(rf"(^|/){re.escape(scope)}(/|$)", path):
            return scope
    return None


def scope_map(hlo_text: str, scopes, ragged_dot_scope=None) -> dict:
    """instruction name -> scope, from a compiled program's text; the Mosaic
    calls libtpu lowers `ragged_dot` to (metadata of its own) go to
    `ragged_dot_scope`."""
    out = {}
    for m in _lm._INSTRUCTION.finditer(hlo_text):
        name, line = m.group(1), m.group(0)
        op = _lm._OP_NAME.search(line)
        scope = scope_of(op.group(1), scopes) if op else None
        if ragged_dot_scope and name.startswith("ragged-dot"):
            scope = ragged_dot_scope
        if scope:
            out[name] = scope
    return out


def _get_path(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def held_counts(idx, num_experts: int, held) -> np.ndarray:
    """(layers, N, k) expert ids -> (layers, held experts) pairs each got."""
    first, count = held
    return np.stack([np.bincount(layer.ravel(), minlength=num_experts)
                     for layer in np.asarray(idx)])[:, first:first + count]


def held_load(idx, num_experts: int, held) -> dict:
    """(layers, N, k) expert ids -> how the pairs fell on the held experts."""
    counts = held_counts(idx, num_experts, held)
    return {"held_max_over_mean": float(np.max(counts.max(1) / np.maximum(counts.mean(1), 1e-9))),
            "held_most": int(counts.max()), "held_fewest": int(counts.min()),
            "held_empty": int(np.sum(counts == 0)),
            "pairs_held_most_in_a_layer": int(counts.sum(1).max()),
            "pairs_held_share": float(counts.sum() / np.asarray(idx).size)}


class ShareStepCheck(check_lm.LMStepCheck):
    """`check_lm.LMStepCheck` for a share of a deployment with router state:
    the reference's steps thread the selection bias (`loss(..., bias)`,
    `bias_update`), the routers are compared on the same input at EVERY step
    (the bias is zero at the first), the bias after the steps must be the
    reference's in nearly every entry, and an expert's slice of the experts'
    leaves is judged apart only above the reference's `EXPERT_PAIRS_FLOOR`
    pairs — those under it are pooled and judged as one."""

    def read_program(self, state, losses, routings, biases):
        """biases: the bias the routers used at each step, then the one the
        last step left."""
        super().read_program(state, losses, routings)
        self.got["biases"] = [np.asarray(b, np.float32) for b in biases]

    def reference_steps(self) -> dict:
        import jax
        import jax.numpy as jnp

        ref, hp = self.ref, self.hp
        device = jax.local_devices()[0]
        grad = jax.jit(jax.value_and_grad(
            lambda p, b, chosen, bias: ref.loss(p, b, hp, chosen, bias), has_aux=True))
        routers_on = jax.jit(lambda p, x, bias: ref.routers_on(p, x, hp, bias))
        adamw = jax.jit(lambda p, g, m, v, t: ref.adamw_step(p, g, m, v, t, hp["adamw"]),
                        donate_argnums=(0, 2, 3))
        zeros = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree))
        losses, routing, same = [], [], []
        with jax.default_matmul_precision("highest"), jax.default_device(device):
            # the reference's trajectory starts from the program's parameters
            # and bias and keeps its own from there
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
                (value, own), grads = grad(params, ref_batch, chosen, bias)
                losses.append(float(value))
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
        return {"losses": np.asarray(losses), "mu": mu, "params": check_lm._host(params),
                "routing": routing, "router_same_input": same,
                "bias": np.asarray(bias, np.float32)}

    def expert_figures(self, want: dict, tolerances: dict) -> tuple:
        """Every held expert's slice of the experts' leaves (all its layers
        together, as `check_lm` takes an expert) apart if it got at least the
        floor of pairs over the compared steps, the others pooled into one
        judged unit: (figures, failures)."""
        floor = self.ref.EXPERT_PAIRS_FLOOR
        held = (self.hp["first_expert"], self.hp["n_routed_experts"])
        pairs = sum(held_counts(r[0], self.hp["num_experts"], held)
                    for r in self.got["routings"])              # (layers, held)
        layers, count = pairs.shape
        apart = pairs.sum(0) >= floor
        figures = {"expert_pairs_fewest": int(pairs.sum(0).min()),
                   "expert_slice_pairs_fewest": int(pairs.min()),
                   "experts_pooled": int(np.sum(~apart))}
        failures = []
        for leaf in check_lm.EXPERT_LEAVES:
            if leaf not in self.params0:
                continue
            for kind, ours, theirs, base in (
                    ("mu", self.got["mu"][leaf], want["mu"][leaf], None),
                    ("update", self.got["params"][leaf], want["params"][leaf],
                     self.params0[leaf])):
                err = np.array([[check_lm._sq_norm(ours[l, e], theirs[l, e])
                                 for e in range(count)] for l in range(layers)])
                size = np.array([[check_lm._sq_norm(theirs[l, e], None if base is None
                                                    else base[l, e])
                                  for e in range(count)] for l in range(layers)])
                each = np.sqrt(err / np.maximum(size, 1e-60))   # per (layer, expert)
                figures[f"{kind}_rel_l2.{leaf}.by_pairs"] = sorted(
                    [int(n), round(float(r), 4)] for n, r in zip(pairs.ravel(), each.ravel()))
                err, size = err.sum(0), size.sum(0)
                units = list(np.sqrt(err[apart] / np.maximum(size[apart], 1e-60)))
                if np.any(~apart):
                    units.append(float(np.sqrt(err[~apart].sum()
                                               / max(size[~apart].sum(), 1e-60))))
                worst = float(max(units))
                limit = tolerances[f"{kind}_rel_l2"]["experts"]
                figures[f"{kind}_rel_l2.{leaf}.worst_judged"] = worst
                if not worst <= limit:
                    failures.append(f"{kind}_rel_l2.{leaf}.worst_judged {worst:.4g} > {limit:.4g}")
        return figures, failures

    def compare(self) -> dict:
        marks = [("start", time.monotonic())]
        want = self.reference_steps()
        marks.append(("reference_steps", time.monotonic()))
        tolerances = self.ref.TOLERANCES
        # check_lm holds the WORST expert to "experts" however few pairs it
        # got; here that figure is reported and `expert_figures`' is judged
        unjudged = {kind: {**tolerances[kind], "experts": float("inf")}
                    for kind in ("mu_rel_l2", "update_rel_l2")}
        verdict = check_lm.compare(self.got, want, self.params0,
                                   {**tolerances, **unjudged})
        figures, failures = self.expert_figures(want, tolerances)
        # The bias moves by the SIGN of mean load − load: an expert whose load
        # is within a pair or two of the mean (384 at the cell's size) takes
        # the other sign when one pair flips between the step's own forward
        # pass and the routing read beside it. So: the share of entries that
        # differ, which a missing, doubled or mis-signed update puts near one.
        off = np.abs(self.got["biases"][-1] - want["bias"]) > 1e-7
        figures["bias_entries_off_share"] = float(np.mean(off))
        figures["bias_abs_max"] = float(np.max(np.abs(want["bias"])))
        if not figures["bias_entries_off_share"] <= tolerances["bias_entries_off_share"]:
            failures.append(f"the selection bias after the steps differs in "
                            f"{figures['bias_entries_off_share']:.3g} of its entries")
        verdict["figures"].update(figures)
        verdict["failures"].extend(failures)
        verdict["ok"] = not verdict["failures"]
        marks.append(("compared", time.monotonic()))
        verdict["figures"]["seconds"] = {
            b[0]: round(b[1] - a[1], 2) for a, b in zip(marks, marks[1:])}
        return verdict


def program_check(trainer, spec, mesh, zoo, reference, model_params, check_batches,
                  fresh_state, say) -> dict:
    """The cell's check: the program's steps on `check_batches`, one step a
    dispatch, read back; its state released; the reference's steps; the
    comparison. Returns `compare()`'s verdict."""
    import jax

    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    assignments = jax.jit(lambda params, bias, toks: zoo.expert_assignments(
        params, bias, toks, spec.model.cfg))
    bias_of = lambda state: _get_path(state.extra_vars, reference.BIAS)
    t = time.monotonic()
    state = fresh_state()
    checker = ShareStepCheck(reference, model_params, check_batches)
    checker.before(state)
    losses, routings, biases = [], [], []
    for step_batch in check_batches:        # one step a dispatch: the routing
        bias = bias_of(state)               # of each step from its own state
        biases.append(jax.device_get(bias))
        routings.append(jax.device_get(
            assignments(state.params, bias, step_batch["features"])))
        state, m = trainer.train_many(state, shard_batch_stack(
            mesh, [step_batch], spec.batch_partition))
        losses.append(m["loss"])
    biases.append(jax.device_get(bias_of(state)))
    checker.read_program(state, np.concatenate(jax.device_get(losses)), routings, biases)
    del state, m            # the reference needs the chip's memory
    say(f"check: the program's {len(check_batches)} steps read back at "
        f"{time.monotonic() - t:.1f} s")
    verdict = checker.compare()
    say(f"check against the reference in {time.monotonic() - t:.1f} s: "
        f"{verdict['figures']}")
    for failure in verdict["failures"]:
        say(f"CHECK FAILED: {failure}")
    return verdict


def _apply_rehearsal(config: dict, traffic: dict) -> None:
    tiny = common.load_json("rehearse", "tiny-lm-share.json")
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
    vocab = hp["vocab_size"]
    t = time.monotonic()
    tokens = _lm.tokens_from_seed(seed, stacks * k * batch, seq_len, vocab,
                                  float(traffic["zipf_s"]))
    say(f"generated {tokens.shape[0]} sequences of {seq_len} + 1 tokens in "
        f"{time.monotonic() - t:.1f} s")
    check_batches = _lm._batches(tokens, batch, 0, check_steps)

    def fresh_state():
        state = trainer.init_state(check_batches[0])
        jax.block_until_ready(state.params)
        return state

    assignments = jax.jit(lambda params, bias, toks: zoo.expert_assignments(
        params, bias, toks, spec.model.cfg))

    def routing_counters(state, toks) -> dict:
        bias = _get_path(state.extra_vars, reference.BIAS)
        idx = assignments(state.params, bias, toks)[0]
        return dict(held_load(idx, hp["num_experts"], held),
                    bias_abs_max=float(np.max(np.abs(np.asarray(bias)))))

    # ---- correct? -------------------------------------------------------- #
    verdict = program_check(trainer, spec, mesh, zoo, reference, model_params,
                            check_batches, fresh_state, say)

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
    scopes = scope_map(hlo_text, flops.SCOPES, flops.RAGGED_DOT_SCOPE)
    mem = exe.memory_analysis()
    say(f"window program compiled or loaded in {time.monotonic() - t:.1f} s: "
        f"{len(scopes)} instructions under a named scope; memory_analysis: "
        f"arguments {mem.argument_size_in_bytes}, outputs {mem.output_size_in_bytes}, "
        f"aliased {mem.alias_size_in_bytes}, temporaries {mem.temp_size_in_bytes} bytes")

    losses_finite = True

    def dispatch(i):
        nonlocal state
        state, metrics = trainer.train_many(state, resident[i % stacks])
        return metrics

    def readback(metrics):
        nonlocal losses_finite
        losses_finite &= bool(np.all(np.isfinite(np.asarray(metrics["loss"]))))

    passes_run = lambda: np.asarray(_get_path(state.extra_vars, reference.PASSES), np.int64)
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
    load_last = routing_counters(state, first_tokens)
    say(f"routing after the window: {load_last}")
    passes = passes_run() - passes_before
    collapsed = bool(np.any(2 * (passes - steps) > steps))
    say(f"passes of the held dispatch in the window's {steps} steps, by sparse-expert "
        f"layer: {passes.tolist()} ({int(np.sum(np.maximum(passes - steps, 0)))} beyond "
        f"one a step{'; COLLAPSED onto the held experts' if collapsed else ''})")

    # ---- shape-derived floors ---------------------------------------------- #
    peaks = None if ctx["rehearse"] else common.peaks(devices[0].device_kind)
    pairs_held = load_last["pairs_held_share"] * hp["moe_layers"] * seq_len \
        * hp["num_experts_per_tok"]
    shape = {
        "model_flops_per_sample": flops.model_flops_per_sample(model_params, seq_len),
        "step_bytes_per_chip": flops.step_bytes(model_params, batch, seq_len) / chips,
        "scan_flops_per_step":
            flops.scan_flops_per_sample(model_params, seq_len) * batch / chips,
        "scan_bytes_per_step":
            flops.scan_bytes_per_sample(model_params, seq_len) * batch / chips,
        "held_expert_matmul_flops_per_step":
            flops.held_expert_matmul_flops(model_params, pairs_held) * batch / chips,
        "gqa_attention_flops_per_step":
            flops.attention_flops_per_sample(model_params, seq_len) * batch / chips,
        "optimizer_bytes_per_chip": flops.optimizer_bytes(model_params),
        "parameters": flops.parameter_count(model_params),
        "seq_len": seq_len,
    }
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
                     "routing_agreement": verdict["figures"].get("routing_agreement"),
                     "router_same_input_agreement":
                         verdict["figures"].get("router_same_input_agreement"),
                     "memory_analysis": {
                         "arguments": mem.argument_size_in_bytes,
                         "outputs": mem.output_size_in_bytes,
                         "aliased": mem.alias_size_in_bytes,
                         "temporaries": mem.temp_size_in_bytes}},
    }
