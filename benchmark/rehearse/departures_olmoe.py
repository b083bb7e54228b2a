"""The readings the OLMoE cell's limits are set from, and the precision control
they are read against, in ONE process on the chip at full width:

    chiprun --chips 1 --timeout 3000 -- python3 benchmark/rehearse/departures_olmoe.py \
        [--seeds a,b,c] [--control_seeds a,b] [--model_params 'k=v;k=v' --seq_len T]

(the last two: a smaller program, for a CPU.) For every seed the program's
check steps run exactly as `drivers/resident_lm.py` runs them (its trainer,
its batches from the seed, one step a dispatch, the routing read before each),
the plain reference follows, and `check_lm.compare` judges: one line a seed
with `correct`, the failures and every figure, and for the experts' leaves the
error of every expert beside the (token, slot) pairs it got (`by_pairs`).

The control (`--control_seeds`) puts the plain reference in the program's
place, computed in the nearest precision below the one the configuration
states for its matmuls (bfloat16 operands): every `@` and `einsum` of
`benchmark/reference/olmoe.py` with both operands rounded through
float8_e4m3 at a per-tensor scale (the largest magnitude at 448), float32
accumulation, straight-through gradients, float32 AdamW — the reference's own
source, its matmuls rewritten, nothing of the program. It must read
`correct: false`. None of this is run by the benchmark; nothing here is an
option of the program.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import sys
import time
import types

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import check_lm, common  # noqa: E402

CELL = "olmoe-1b-7b.resident-4k"


class _Matmuls(ast.NodeTransformer):
    """`a @ b` -> `_mm(a, b)`, `jnp.einsum(spec, a, b)` -> `_es(spec, a, b)`."""

    def visit_BinOp(self, node):
        self.generic_visit(node)
        if isinstance(node.op, ast.MatMult):
            return ast.copy_location(ast.Call(
                ast.Name("_mm", ast.Load()), [node.left, node.right], []), node)
        return node

    def visit_Call(self, node):
        self.generic_visit(node)
        f = node.func
        if (isinstance(f, ast.Attribute) and f.attr == "einsum"
                and isinstance(f.value, ast.Name) and f.value.id == "jnp"):
            return ast.copy_location(ast.Call(
                ast.Name("_es", ast.Load()), node.args, node.keywords), node)
        return node


def reference_in_float8():
    """The reference module with every matmul's operands rounded through
    float8_e4m3 (per-tensor scale, straight-through gradient)."""
    import jax
    import jax.numpy as jnp

    def q8(x):
        top = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
        scale = jnp.where(top > 0, top / 448.0, 1.0)
        rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
        return x + jax.lax.stop_gradient(rounded - x)

    path = os.path.join(common.ROOT, "benchmark", "reference", "olmoe.py")
    with open(path) as f:
        tree = ast.fix_missing_locations(_Matmuls().visit(ast.parse(f.read())))
    module = types.ModuleType("reference_olmoe_float8")
    module._mm = lambda a, b: q8(a) @ q8(b)
    module._es = lambda spec, a, b: jnp.einsum(spec, q8(a), q8(b))
    exec(compile(tree, path, "exec"), module.__dict__)
    return module


def control_steps(lowp, hp, params0, batches) -> dict:
    """What `LMStepCheck.read_program` would hold had `lowp` been the program:
    its losses, first moments, parameters and, before each step, its routers'
    own choice, weights and input."""
    import jax
    import jax.numpy as jnp

    k = hp["num_experts_per_tok"]

    def routing(params, tokens):
        x = params["embed"][tokens]
        idx, weights, inputs = [], [], []
        for layer in range(hp["num_hidden_layers"]):
            p = lowp._layer(params, layer)
            x = x + lowp.attention(p, x, hp)
            inputs.append(x)
            h, _, probs, _ = lowp.router(p, x, hp)
            top_w, top_i = jax.lax.top_k(probs, k)
            idx.append(top_i)
            weights.append(top_w)
            x = x + lowp.experts(
                p, h, jnp.zeros_like(probs).at[
                    jnp.arange(probs.shape[0])[:, None], top_i].set(top_w)).reshape(x.shape)
        return jnp.stack(idx), jnp.stack(weights), jnp.stack(inputs)

    device = jax.local_devices()[0]
    grad = jax.jit(jax.value_and_grad(lambda p, b: lowp.loss(p, b, hp), has_aux=True))
    routing = jax.jit(routing)
    adamw = jax.jit(lowp.adamw_step, donate_argnums=(0, 2, 3))
    zeros = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree))
    losses, routings = [], []
    with jax.default_matmul_precision("highest"), jax.default_device(device):
        params = jax.device_put(params0, device)
        mu = nu = None
        for i, batch in enumerate(batches):
            tokens = jnp.asarray(batch["features"], jnp.int32)
            routings.append(tuple(np.asarray(a) for a in routing(params, tokens)))
            (value, _), grads = grad(params, {
                "tokens": tokens, "labels": jnp.asarray(batch["labels"], jnp.int32),
                "mask": jnp.asarray(batch["mask"], jnp.float32)})
            losses.append(float(value))
            if mu is None:
                mu, nu = zeros(params), zeros(params)
            else:
                mu, nu = jax.device_put((mu, nu), device)
            params, mu, nu = adamw(params, grads, mu, nu, jnp.float32(i + 1))
            del grads
            mu, nu = check_lm._host(mu), check_lm._host(nu)
    return {"losses": np.asarray(losses, np.float64), "mu": mu,
            "params": check_lm._host(params), "routings": routings}


def by_pairs(checker, want) -> dict:
    """For each of the experts' leaves and both figures, [pairs, error] of
    every expert, fewest pairs first."""
    experts = checker.hp["num_experts"]
    pairs = sum(np.bincount(np.asarray(r[0]).ravel(), minlength=experts)
                for r in checker.got["routings"])
    out = {"pairs_fewest": int(pairs.min()), "pairs_most": int(pairs.max())}
    for leaf in check_lm.EXPERT_LEAVES:
        for kind, ours, theirs, base in (
                ("mu", checker.got["mu"][leaf], want["mu"][leaf], None),
                ("update", checker.got["params"][leaf], want["params"][leaf],
                 checker.params0[leaf])):
            each = check_lm._rel_l2_by_expert(ours, theirs, base)
            out[f"{kind}_rel_l2.{leaf}.by_pairs"] = sorted(
                [int(n), round(float(e), 5)] for n, e in zip(pairs, each))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", default="157194244,2147483498")
    ap.add_argument("--control_seeds", default="")
    ap.add_argument("--model_params", default="")
    ap.add_argument("--seq_len", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(
        common.OUT_DIR, "departures_olmoe.jsonl"))
    args = ap.parse_args(argv)

    import jax

    resolved = common.resolve_cell(CELL)
    config, traffic = resolved["config"], resolved["traffic"]
    if args.model_params:
        params = common.model_params(config)
        params.update(dict(kv.split("=") for kv in args.model_params.split(";")))
        config["model_params"] = common.format_model_params(params)
    seq_len = args.seq_len or int(traffic["seq_len"])
    model_params = common.model_params(config)
    batch, check_steps = int(traffic["batch_per_chip"]), int(traffic["check_steps"])
    _resident = common.load_module("drivers", "resident")
    driver = common.load_module("drivers", "resident_lm")
    reference = common.load_module("reference", common.model_name(config))
    from elasticdl_tpu.common.runtime import configure_jax_runtime
    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    devices = jax.devices()[:1]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    out = open(args.out, "a")

    def report(name, seed, verdict, extra):
        figures = {k: v for k, v in verdict["figures"].items()
                   if not k.startswith("losses_")}
        line = {"run": name, "seed": seed, "correct": bool(verdict["ok"]),
                "failures": verdict["failures"], "figures": figures, **extra}
        out.write(json.dumps(line) + "\n")
        out.flush()
        short = {k: (round(v, 5) if isinstance(v, float) else v)
                 for k, v in figures.items() if isinstance(v, (int, float))}
        print(f"{name} seed {seed}: correct {line['correct']} "
              f"failures {line['failures']} {short} "
              f"pairs {extra.get('pairs_fewest')}..{extra.get('pairs_most')}", flush=True)

    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    lowp = reference_in_float8() if controls else None
    for seed in dict.fromkeys(seeds + controls):
        t = time.monotonic()
        cfg, spec, mesh, trainer = _resident.build_trainer(config, devices, seed)
        configure_jax_runtime(cfg)
        zoo = sys.modules[spec.module_name]
        tokens = driver.tokens_from_seed(
            seed, int(traffic["distinct_stacks"]) * int(traffic["steps_per_dispatch"]) * batch,
            seq_len, int(model_params["vocab_size"]), float(traffic["zipf_s"]))
        batches = driver._batches(tokens, batch, 0, check_steps)
        assignments = jax.jit(
            lambda params, toks: zoo.expert_assignments(params, toks, spec.model.cfg))
        state = trainer.init_state(batches[0])
        checker = check_lm.LMStepCheck(reference, model_params, batches)
        checker.before(state)
        losses, routings = [], []
        for step_batch in batches:
            routings.append(jax.device_get(
                assignments(state.params, step_batch["features"])))
            state, m = trainer.train_many(state, shard_batch_stack(
                mesh, [step_batch], spec.batch_partition))
            losses.append(m["loss"])
        checker.read_program(state, np.concatenate(jax.device_get(losses)), routings)
        del state, m
        want = checker.reference_steps()
        if seed in seeds:
            verdict = check_lm.compare(checker.got, want, checker.params0,
                                       reference.TOLERANCES)
            report("program", seed, verdict, by_pairs(checker, want))
        if seed in controls:
            checker.got = control_steps(lowp, checker.hp, checker.params0, batches)
            # the reference computes with the CONTROL's choice of experts, as
            # it does with the program's
            want = checker.reference_steps()
            verdict = check_lm.compare(checker.got, want, checker.params0,
                                       reference.TOLERANCES)
            report("reference_in_float8", seed, verdict, by_pairs(checker, want))
        print(f"seed {seed} took {time.monotonic() - t:.1f} s", flush=True)
        del checker, want, trainer
    out.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
