"""The departures the LFM2-8B-A1B cell's check must catch and the precision
controls its limits are read against, each as a patch of the PROGRAM (the zoo
module and the operations it calls), and a command that runs the cell's check
— the driver's own `program_check` — under each of them on the chip at full
width:

    chiprun --chips 1 --timeout 3300 -- python3 benchmark/rehearse/departures_lfm2_moe.py \
        [--seed N] [--only name,name] [--seeds a,b,c] [--check_steps 2] [--held_share] \
        [--seq_len 16384]

`CONTROLS` keep what the configuration states float32 in bfloat16 (the
convolution mixer's planes); `REFERENCE_CONTROLS` put the plain reference,
computed in bfloat16, in the program's place: its two steps, on the program's
own routing, are compared with the reference's own as the program's are.
`REPORTED` is run and printed and NOT required to fail: the renormaliser's
1e-6 put back to 1e-20 is a few float32 ulps of the weights.

Every line it prints holds `correct: true|false`, the failures and every
figure of the comparison. The unpatched program must read true, every
departure and the reference control false; the program's own control is in
`BELOW_THE_NOISE_ON_THE_CHIP`, with its figures. `--held_share` prints, after the settling,
the share of every sparse layer's pairs that each quarter of the experts
receives (the configuration's `assumed.held_share`). The CPU tests
(`tests/test_lfm2_moe_check.py`) apply the same patches at the tiny preset.
None of this is run by the benchmark; nothing here is an option of the
program.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402

_afmoe = common.load_module("rehearse", "departures_afmoe")
fresh_trainer, _rounded, held_shares = (
    _afmoe.fresh_trainer, _afmoe._rounded, _afmoe.held_shares)


def _blocks(bgu):
    c = bgu.shape[-1] // 3
    return bgu[..., :c], bgu[..., c:2 * c], bgu[..., 2 * c:]


def _gate_g_left_out(zoo, moe_ops, ssm, jnp, jax):
    """conv₃(B ⊙ u) goes on ungated."""
    def gated_conv(bgu, weight):
        b, _, u = _blocks(bgu)
        return ssm.causal_conv1d(b * u, weight)
    return [(zoo, "gated_conv", gated_conv)]


def _blocks_permuted(zoo, moe_ops, ssm, jnp, jax):
    """The projection's column blocks read as (G, B, u): B gates the output, G
    the input (B and u alone commute)."""
    plain = zoo.gated_conv

    def gated_conv(bgu, weight):
        b, g, u = _blocks(bgu)
        return plain(jnp.concatenate([g, b, u], axis=-1), weight)
    return [(zoo, "gated_conv", gated_conv)]


def _tap_dropped(zoo, moe_ops, ssm, jnp, jax):
    """The earliest of the K taps multiplied by nothing: a convolution of
    K − 1."""
    plain = zoo.gated_conv
    return [(zoo, "gated_conv", lambda bgu, weight: plain(bgu, weight.at[0].set(0.0)))]


def _head_norms_left_out(zoo, moe_ops, ssm, jnp, jax):
    return [(zoo, "qk_norm", lambda p, q, k, cfg: (q * p["q_norm"], k * p["k_norm"]))]


def _route_with(change):
    """A router whose (chosen scores, bias of the chosen, scale, eps) ->
    weights rule is `change`'s."""
    def patch(zoo, moe_ops, ssm, jnp, jax):
        def sigmoid_topk_route(logits, bias, k, scale, eps=1e-20):
            scores = jax.nn.sigmoid(logits.astype(jnp.float32))
            _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
            chosen = jnp.take_along_axis(scores, idx, axis=-1)
            chosen_bias = jnp.take_along_axis(
                jnp.broadcast_to(bias.astype(jnp.float32), scores.shape), idx, axis=-1)
            return scores, change(jnp, chosen, chosen_bias, scale, eps), idx
        return [(moe_ops, "sigmoid_topk_route", sigmoid_topk_route)]
    return patch


def _renormalised(jnp, chosen, scale, eps):
    return scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + eps)


def _one_held_expert_left_out(zoo, moe_ops, ssm, jnp, jax):
    """The last expert of the held range adds nothing."""
    plain = moe_ops.dropless_moe

    def dropless_moe(x, expert_idx, weights, experts, held=None, num_experts=0, **more):
        last = (held[0] + held[1] if held else experts[0].shape[0]) - 1
        return plain(x, expert_idx, jnp.where(expert_idx == last, 0.0, weights), experts,
                     held=held, num_experts=num_experts, **more)
    return [(moe_ops, "dropless_moe", dropless_moe)]


def _conv_planes_in_bfloat16(zoo, moe_ops, ssm, jnp, jax):
    """Every plane the mixer states float32 between its two matmuls written in
    bfloat16, as an implementation that keeps its activations in bfloat16
    holds them: the projection's three blocks, B ⊙ u, the convolution's
    output and the gated result."""
    r = lambda x: _rounded(x, jax)

    def gated_conv(bgu, weight):
        b, g, u = _blocks(r(bgu))
        return r(g * r(ssm.causal_conv1d(r(b * u), weight)))
    return [(zoo, "gated_conv", gated_conv)]


# the nearest precision below the stated one, where the statement is float32
CONTROLS = {"conv_planes_in_bfloat16": _conv_planes_in_bfloat16}
# name -> what of the reference's `hyper` changes: everything the
# configuration states float32 computed in bfloat16, from float32 master
# weights and moments
REFERENCE_CONTROLS = {"reference_in_bfloat16": {"dtype": "bfloat16"}}
DEPARTURES = {
    "gate_g_left_out": _gate_g_left_out,
    "blocks_permuted": _blocks_permuted,
    "tap_dropped": _tap_dropped,
    "head_norms_left_out": _head_norms_left_out,
    "bias_used_as_a_weight": _route_with(
        lambda jnp, chosen, bias, scale, eps: _renormalised(jnp, chosen + bias, scale, eps)),
    "one_held_expert_left_out": _one_held_expert_left_out,
}
# What the check on the chip could NOT tell from the program as it is at full
# width (my chip runs, PR 62, seed 2147483777; PERF.md §6), (the case, the
# program as it is): the mixer's planes in bfloat16 raise every leaf's first
# moment by 1.17 times and nothing by more, where the program as it is spans
# 1.16 times over eight seeds (conv_in 0.0149-0.0173) — B, G, u and the gated result are read next by a matmul that rounds
# its operand to bfloat16 anyway, and the products' own rounding is a few
# parts in a thousand of numbers of order 1e-3. The CPU test, float32 against
# float32, catches it (`tests/test_lfm2_moe_check.py`).
BELOW_THE_NOISE_ON_THE_CHIP = {
    "conv_planes_in_bfloat16": {"mu_rel_l2.conv_in": (0.02026, 0.01694),
                                "mu_rel_l2.mlp_down": (0.01718, 0.01429),
                                "mu_rel_l2.moe_router": (0.07781, 0.06362),
                                "routing_agreement": (0.98956, 0.99143),
                                "loss_rel": (1.07e-5, 2.6e-6)},
}
# run, printed, and not required to fail
REPORTED = {
    "renormaliser_1e-20": _route_with(
        lambda jnp, chosen, bias, scale, eps: _renormalised(jnp, chosen, scale, 1e-20)),
}
ALL = {**DEPARTURES, **CONTROLS, **REPORTED}


@contextlib.contextmanager
def applied(name, zoo):
    """The program with departure `name` patched in (None: as it is)."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import moe as moe_ops
    from elasticdl_tpu.ops import ssm

    patches = ALL[name](zoo, moe_ops, ssm, jnp, jax) if name else []
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    for obj, attr, new in patches:
        setattr(obj, attr, new)
    try:
        yield
    finally:
        for obj, attr, old in saved:
            setattr(obj, attr, old)


@contextlib.contextmanager
def keeping_the_checker(driver):
    """Inside it every `ModelStepCheck` that compares is appended to the list
    this yields: what a reference control needs of the program as it is (its
    starting point, its routing, its bias)."""
    kept, plain = [], driver.ModelStepCheck.compare
    driver.ModelStepCheck.compare = lambda self: kept.append(self) or plain(self)
    try:
        yield kept
    finally:
        driver.ModelStepCheck.compare = plain


def reference_in_the_program_s_place(name, driver, reference, model_params, batches,
                                     program) -> dict:
    """`compare()`'s verdict with the reference's own steps under
    `REFERENCE_CONTROLS[name]` standing where the program's are read: the
    same starting point, the same routing (`program`: a `ModelStepCheck` that
    has read the program as it is) and the selection bias that routing
    leaves."""
    import numpy as np

    low = driver.ModelStepCheck(reference, model_params, batches)
    low.hp = {**low.hp, **REFERENCE_CONTROLS[name]}
    low.params0, low.got = program.params0, program.got
    got = low.reference_steps()
    checker = driver.ModelStepCheck(reference, model_params, batches)
    checker.params0 = program.params0
    checker.got = {
        **program.got, "losses": np.asarray(got["losses"], np.float64),
        "mu": {k: np.asarray(v, np.float32) for k, v in got["mu"].items()},
        "params": {k: np.asarray(v, np.float32) for k, v in got["params"].items()},
        "biases": program.got["biases"][:-1] + [got["bias"]],
        "terms": {k: np.asarray(v, np.float64) for k, v in low.want_terms.items()}}
    return checker.compare()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="lfm2-8b-a1b.resident-32k")
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--seeds", default="",
                    help="run `--only` (default: the program AS IT IS) at each of these "
                         "seeds and print every figure: what the tolerances are derived from")
    ap.add_argument("--only", default="")
    ap.add_argument("--check_steps", type=int, default=0)
    ap.add_argument("--seq_len", type=int, default=0)
    ap.add_argument("--model_params", default="",
                    help="k=v;k=v over the configuration's: a smaller program, on a CPU")
    ap.add_argument("--held_share", action="store_true",
                    help="print the four shares' part of every layer's pairs after "
                         "the settling, and run no check")
    args = ap.parse_args(argv)

    if args.seeds:
        return sum(main(["--workload", args.workload, "--seed", seed,
                         "--only", args.only or "none",
                         "--check_steps", str(args.check_steps),
                         "--seq_len", str(args.seq_len),
                         "--model_params", args.model_params])
                   for seed in args.seeds.split(","))
    resolved = common.resolve_cell(args.workload)
    config, traffic = resolved["config"], resolved["traffic"]
    if args.model_params:
        config["model_params"] = common.format_model_params({
            **common.model_params(config),
            **common.model_params({"model_params": args.model_params})})
    driver = common.load_module("drivers", traffic["driver"])
    model_params = common.model_params(config)
    reference = common.load_module("reference", common.model_name(config))
    steps = args.check_steps or int(traffic["check_steps"])
    batch = int(traffic["batch_per_chip"])
    tokens = driver._lm.tokens_from_seed(
        args.seed, steps * batch, args.seq_len or int(traffic["seq_len"]),
        int(model_params["vocab_size"]), float(traffic["zipf_s"]))
    batches = driver._lm._batches(tokens, batch, 0, steps)
    names = [None if n == "none" else n for n in args.only.split(",") if n] \
        or [None] + sorted(REFERENCE_CONTROLS) + sorted(CONTROLS) + sorted(DEPARTURES) \
        + sorted(REPORTED)
    hp = reference.hyper(model_params)
    held = (hp["first_expert"], hp["n_routed_experts"])
    settle = int(traffic["settle_router_steps"])
    if args.held_share:
        import jax

        spec, _, trainer, zoo = fresh_trainer(driver, config, args.seed)
        state = driver.settled_state_maker(
            trainer, zoo, spec, reference, batches, settle, held, print)()
        bias = driver._share._get_path(state.extra_vars, reference.BIAS)
        idx = jax.device_get(driver._assignments(zoo, spec)(
            state.params, bias, batches[0]["features"])[0])
        shares = hp["num_experts"] // hp["n_routed_experts"]
        print(f"seed {args.seed}, after {settle} settling passes: the share of each sparse "
              f"layer's pairs on each of the {shares} shares of {hp['n_routed_experts']} "
              f"experts: {held_shares(idx, hp['num_experts'], shares).round(5).tolist()}")
        return 0
    fresh, as_it_is = None, []
    wrong = 0
    for name in names:
        # a new trainer every time: the patched functions must be traced anew
        spec, mesh, trainer, zoo = fresh_trainer(driver, config, args.seed)
        if fresh is None:
            # the selection bias as the cell settles it, by the program AS IT
            # IS, once a seed: every departure starts from the same state
            fresh = driver.settled_state_maker(
                trainer, zoo, spec, reference, batches, settle, held, lambda text: None)
        # a reference control stands where the program as it is stood: that
        # program's check is kept the first time it runs
        patch = None if name in REFERENCE_CONTROLS else name
        if patch is not None or not as_it_is:
            with applied(patch, zoo), keeping_the_checker(driver) as kept:
                verdict = driver.program_check(
                    trainer, spec, mesh, zoo, reference, model_params, batches,
                    fresh, lambda text: None)
            if patch is None:
                as_it_is = kept
        if name in REFERENCE_CONTROLS:
            verdict = reference_in_the_program_s_place(
                name, driver, reference, model_params, batches, as_it_is[0])
        expected = name is None or name in BELOW_THE_NOISE_ON_THE_CHIP
        unexpected = verdict["ok"] != expected and name not in REPORTED
        wrong += unexpected
        print(f"seed {args.seed} {name or 'the program as it is'}: correct: "
              f"{'true' if verdict['ok'] else 'false'}"
              f"{'  <-- UNEXPECTED' if unexpected else ''}"
              f"{'  (reported, not required to fail)' if name in REPORTED else ''}; "
              f"failures: {verdict['failures']}; figures: {verdict['figures']}", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    raise SystemExit(main())
