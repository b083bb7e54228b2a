"""Driver `resident_lm`: a language model's device step alone, the input path
bypassed — `drivers/resident.py`'s method on token sequences.

This process builds the mesh, the zoo model through `ModelSpec` and the
`Trainer` as `Worker._build_trainer` does, initialises the state on the device
from `--seed`, and runs `Trainer.train_many` over stacked batches that already
live on the device, rotating a few distinct stacks, with one read-back of the
losses per dispatch. Closed loop, one client. Every dispatch is one reading of
the rate (a sample is one sequence), from the end of the previous read-back to
the end of its own; the window's rate is the median reading.

Set-up: token sequences from the seed (`zipf-tokens`: Zipf's law over the
vocabulary, ids scrambled, labels the sequence shifted by one), the state, the
correctness check against the plain reference (`benchmark/check_lm.py`; the
program's state is released while the reference runs and initialised again
from the seed), the window's program compiled ahead of time (its HLO text
names the scope of every instruction), the stacks' transfer, one warm-up
dispatch. Then the window. With `--trace 1` a few more dispatches run under
the profiler and device time is summed by `jax.named_scope`.

Counters printed and returned: pairs per expert (max / mean) at the window's
start and end, the check's routing agreement, peak memory in use and reserved.
"""

from __future__ import annotations

import os
import re
import statistics
import sys
import time

import numpy as np

from benchmark import common

_resident = common.load_module("drivers", "resident")

# The scopes the program names (model_zoo/transformer/olmoe.py, ops/moe.py,
# training/trainer.py), most specific first: an instruction belongs to the
# first whose name its `op_name` carries.
SCOPES = ("olmoe/moe/router", "olmoe/moe/dispatch", "olmoe/moe/experts",
          "olmoe/moe/combine", "olmoe/moe", "olmoe/attn", "olmoe/head_loss",
          "optimizer", "olmoe")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*$", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def tokens_from_seed(seed: int, sequences: int, seq_len: int, vocab: int,
                     zipf_s: float) -> np.ndarray:
    """(sequences, seq_len + 1) int32 token ids: ranks drawn from the Zipf
    law p(r) ∝ r^-s over the whole vocabulary, then mapped through a
    permutation of the ids drawn from the same seed."""
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(np.arange(1, vocab + 1, dtype=np.float64) ** -zipf_s)
    ranks = np.searchsorted(cdf / cdf[-1], rng.random((sequences, seq_len + 1)))
    return rng.permutation(vocab)[np.minimum(ranks, vocab - 1)].astype(np.int32)


def _batches(tokens: np.ndarray, batch: int, first: int, count: int) -> list:
    out = []
    for i in range(first, first + count):
        rows = tokens[i * batch:(i + 1) * batch]
        out.append({"features": rows[:, :-1], "labels": rows[:, 1:],
                    "mask": np.ones((batch,), np.float32)})
    return out


def scope_of(op_name: str):
    """The scope an instruction's `op_name` metadata puts it in, or None.
    Backward instructions carry the same path inside `transpose(jvp(...))`."""
    path = op_name.replace("transpose(", "").replace("jvp(", "").replace(")", "")
    for scope in SCOPES:
        if re.search(rf"(^|/){re.escape(scope)}(/|$)", path):
            return scope
    return None


def scope_map(hlo_text: str) -> dict:
    """instruction name -> scope, from a compiled program's text. libtpu
    lowers `ragged_dot` to Mosaic calls whose metadata it writes itself
    (`op_name="ragged-dot-none"`): the program's only ragged dots are the
    experts' grouped matmuls, so those names go to `olmoe/moe/experts`."""
    out = {}
    for m in _INSTRUCTION.finditer(hlo_text):
        name, line = m.group(1), m.group(0)
        op = _OP_NAME.search(line)
        scope = scope_of(op.group(1)) if op else None
        if name.startswith("ragged-dot"):
            scope = "olmoe/moe/experts"
        if scope:
            out[name] = scope
    return out


def seconds_by_scope(per_op_s: dict, scopes: dict) -> dict:
    """Device seconds of a trace's operations (`trace_reduce`'s `per_op_s`,
    keyed by the event's HLO text) summed by scope; what no scope claims goes
    to `unattributed`."""
    out = {}
    for text, seconds in per_op_s.items():
        name = text.lstrip("%").split(" ", 1)[0]
        scope = scopes.get(name, "unattributed")
        out[scope] = out.get(scope, 0.0) + seconds
    return out


def seconds_by_kernel(per_op_s: dict, prefix: str) -> float:
    """Device seconds of the custom calls whose instruction name starts with
    `prefix` (a Pallas kernel's `name=`)."""
    return sum(seconds for text, seconds in per_op_s.items()
               if text.lstrip("%").startswith(prefix))


def _apply_rehearsal(config: dict, traffic: dict) -> None:
    tiny = common.load_json("rehearse", "tiny-lm.json")
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

    # ---- sequences, from the seed ---------------------------------------- #
    batch = int(traffic["batch_per_chip"]) * chips
    seq_len = int(traffic["seq_len"])
    k = int(traffic["steps_per_dispatch"])
    stacks = int(traffic["distinct_stacks"])
    check_steps = int(traffic["check_steps"])
    model_params = common.model_params(config)
    vocab = int(model_params["vocab_size"])
    t = time.monotonic()
    tokens = tokens_from_seed(seed, stacks * k * batch, seq_len, vocab,
                              float(traffic["zipf_s"]))
    say(f"generated {tokens.shape[0]} sequences of {seq_len} + 1 tokens in "
        f"{time.monotonic() - t:.1f} s")

    check_batches = _batches(tokens, batch, 0, check_steps)

    def fresh_state():
        state = trainer.init_state(check_batches[0])
        jax.block_until_ready(state.params)
        return state

    t = time.monotonic()
    state = fresh_state()
    say(f"state initialised in {time.monotonic() - t:.1f} s")
    assignments = jax.jit(
        lambda params, toks: zoo.expert_assignments(params, toks, spec.model.cfg))

    def expert_load(params, toks) -> dict:
        idx = np.asarray(assignments(params, toks)[0])
        counts = np.stack([np.bincount(layer.ravel(), minlength=int(
            model_params["num_experts"])) for layer in idx])
        return {"max_over_mean": float(np.max(counts.max(1) / counts.mean(1))),
                "empty_experts": int(np.sum(counts == 0))}

    # ---- correct? -------------------------------------------------------- #
    from benchmark import check_lm

    t = time.monotonic()
    reference = common.load_module("reference", common.model_name(config))
    checker = check_lm.LMStepCheck(reference, model_params, check_batches)
    checker.before(state)
    losses, routings = [], []
    for step_batch in check_batches:        # one step a dispatch: the routing
        routings.append(jax.device_get(     # of each step from its own parameters
            assignments(state.params, step_batch["features"])))
        state, m = trainer.train_many(state, shard_batch_stack(
            mesh, [step_batch], spec.batch_partition))
        losses.append(m["loss"])
    checker.read_program(state, np.concatenate(jax.device_get(losses)), routings)
    del state, m            # the reference needs the chip's memory
    say(f"check: the program's {check_steps} steps read back at "
        f"{time.monotonic() - t:.1f} s")
    verdict = checker.compare()
    say(f"check against the reference in {time.monotonic() - t:.1f} s: "
        f"{verdict['figures']}")
    for failure in verdict["failures"]:
        say(f"CHECK FAILED: {failure}")
    del checker

    # ---- the window's state and stacks, resident -------------------------- #
    t = time.monotonic()
    state = fresh_state()
    resident = [
        shard_batch_stack(mesh, _batches(tokens, batch, s * k, k),
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
    scopes = scope_map(hlo_text)
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

    t = time.monotonic()
    readback(dispatch(0))                   # warm-up: this shape, no other
    load_first = expert_load(state.params, first_tokens)
    say(f"warm-up dispatch in {time.monotonic() - t:.1f} s; pairs per expert "
        f"after it: {load_first}")

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
        f"most {each[-1]:.4f} s; {steps * batch / wall / chips:.2f} samples/s/chip "
        f"= {steps * batch * seq_len / wall / chips:.0f} tokens/s/chip over the "
        f"whole wall); {compiled_in_window} compilation(s) inside it")
    load_last = expert_load(state.params, first_tokens)
    say(f"pairs per expert after the window: {load_last}")

    # ---- shape-derived floors ---------------------------------------------- #
    flops = common.load_module("flops", common.model_name(config))
    peaks = None if ctx["rehearse"] else common.peaks(devices[0].device_kind)
    shape = {
        "model_flops_per_sample": flops.model_flops_per_sample(model_params, seq_len),
        "step_bytes_per_chip": flops.step_bytes(model_params, batch, seq_len) / chips,
        "expert_matmul_flops_per_step":
            flops.expert_matmul_flops_per_sample(model_params, seq_len) * batch / chips,
        "attention_flops_per_step":
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
            traced["scope_s"] = seconds_by_scope(per_op_s, scopes)
            traced["flash_attention_s"] = seconds_by_kernel(per_op_s, "flash_attention")
            say(f"trace of {n * k} steps reduced: "
                f"{ {a: b for a, b in traced.items() if a not in ('device_ops', 'idle_gaps')} }")
        else:
            say("the trace holds no TPU plane: nothing to reduce")

    memory = _resident.device_memory(devices, say)
    say(f"peak memory {memory['memory_peak_bytes'] / 2 ** 30:.2f} GiB")

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
        "counters": {"expert_load_first": load_first, "expert_load_last": load_last,
                     "routing_agreement": verdict["figures"].get("routing_agreement"),
                     "router_same_input_agreement":
                         verdict["figures"].get("router_same_input_agreement")},
    }
