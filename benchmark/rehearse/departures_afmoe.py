"""The departures the Trinity-Mini cell's check must catch and the precision
controls its limits are read against (`CONTROLS`: what the configuration
states float32, kept in bfloat16), each as a patch of the PROGRAM (the zoo
module and the operations it calls), and a command that runs the cell's check
— the driver's own `program_check` — under each of them on the chip at full
width:

    chiprun --chips 1 --timeout 3300 -- python3 benchmark/rehearse/departures_afmoe.py \
        [--seed N] [--only name,name] [--seeds a,b,c] [--check_steps 2] [--held_share] \
        [--warmup_steps 1]

Every line it prints holds `correct: true|false`, the failures and every
figure of the comparison. The unpatched program must read true, every
departure and every control false, but for `BELOW_THE_NOISE_ON_THE_CHIP`: what
this check cannot see at full width on seeded weights (it reads true there,
and says so; the CPU tests, at a window of 8 keys and in float32, catch each).
`--held_share` prints, after the settling, the share of every sparse layer's
pairs that each eighth of the experts receives (the configuration's
`assumed.held_share`). The CPU tests (`tests/test_afmoe_check.py`) apply the
same patches at the tiny preset. None of this is run by the benchmark; nothing
here is an option of the program. The routers' patches are
`departures_nemotron_h.py`'s (one router).
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402

_glm = common.load_module("rehearse", "departures_glm4_moe_lite")
_inside, fresh_trainer = _glm._inside, _glm.fresh_trainer
_route_with, _renormalised, _rounded = _glm._route_with, _glm._renormalised, _glm._rounded


def _gate(how: str):
    """The output gate left out, taken from the un-normed residual stream, or
    applied (its first C columns) AFTER the output projection."""
    def patch(zoo, moe_ops, jnp, jax):
        plain_attention, plain_gate = zoo.attention, zoo.gate

        def gate_of(p, h, cfg):
            return jax.nn.sigmoid(zoo.matmul(
                h, p["wg"], jnp.dtype(cfg.compute_dtype), jnp.float32))

        def ungated(p, h, out, cfg):
            return out.astype(jnp.float32), jnp.full((out.shape[0],), 0.5, jnp.float32)

        if how == "left_out":
            return [(zoo, "gate", ungated)]

        def attention(p, x, kind, cfg):
            if how == "from_the_stream":
                # the gate reads x, everything else the normed h
                zoo.gate = lambda p, h, out, cfg: plain_gate(p, x, out, cfg)
                try:
                    return plain_attention(p, x, kind, cfg)
                finally:
                    zoo.gate = plain_gate
            zoo.gate = ungated
            try:
                y, _ = plain_attention(p, x, kind, cfg)
            finally:
                zoo.gate = plain_gate
            g = gate_of(p, zoo.rmsnorm(x, p["attn_norm"], cfg.rms_norm_eps), cfg)
            return y * g[..., :y.shape[-1]], jnp.mean(g, axis=(1, 2))

        return [(zoo, "attention", attention)]
    return patch


def _positions(rotate_kinds):
    """Rotary positions on the layers of `rotate_kinds`, whatever the
    published kinds say."""
    def patch(zoo, moe_ops, jnp, jax):
        def positions(q, k, kind, cfg):
            if kind not in rotate_kinds:
                return q, k
            return zoo.rope(q, cfg.rope_theta), zoo.rope(k, cfg.rope_theta)
        return [(zoo, "positions", positions)]
    return patch


def _qk_norm_left_out(zoo, moe_ops, jnp, jax):
    return [(zoo, "qk_norm", lambda p, q, k, cfg: (q * p["q_norm"], k * p["k_norm"]))]


def _qk_norm_after_the_rotation(zoo, moe_ops, jnp, jax):
    """rotate(q) then norm: the same OUTPUT while the norms' weights are one,
    as the seed leaves them (a rotation keeps a head's length) — but another
    function of those weights, whose gradient tells (0.64 of the reference's
    on the chip, my chip run, PR 44)."""
    plain_norm, plain_positions = zoo.qk_norm, zoo.positions
    held = {}

    def qk_norm(p, q, k, cfg):
        held["p"] = p
        return q, k

    def positions(q, k, kind, cfg):
        q, k = plain_positions(q, k, kind, cfg)
        return plain_norm(held["p"], q, k, cfg)

    return [(zoo, "qk_norm", qk_norm), (zoo, "positions", positions)]


def _post_norm_left_out(which: str):
    def patch(zoo, moe_ops, jnp, jax):
        return [(zoo, which, lambda p, y, cfg: y * p[which])]
    return patch


def _embedding_multiplier_left_out(zoo, moe_ops, jnp, jax):
    return [(zoo, "embed", lambda params, tokens, cfg: jnp.take(
        params["embed"], tokens, axis=0).astype(jnp.float32))]


def _window(by: int):
    """The sliding layers see `sliding_window + by` keys."""
    def patch(zoo, moe_ops, jnp, jax):
        return _inside(zoo, "attention", "full_attention", lambda plain, cfg: (
            lambda q, k, v, causal=True, window=None: plain(
                q, k, v, causal=causal, window=None if window is None else window + by)))
    return patch


def _shared_expert_left_out(zoo, moe_ops, jnp, jax):
    # inside `moe` the gated unit is the shared expert's alone
    return _inside(zoo, "moe", "gated_mlp", lambda plain, cfg: (
        lambda h, *w: jnp.zeros(h.shape, jnp.float32)))


def _bf16_router(zoo, moe_ops, jnp, jax):
    def route(p, x, bias, cfg):
        h = zoo.rmsnorm(x, p["mlp_norm"], cfg.rms_norm_eps).reshape(-1, x.shape[-1])
        logits = jnp.dot(h.astype(jnp.bfloat16),
                         p["moe_router"].astype(jnp.bfloat16)).astype(jnp.float32)
        _, weights, idx = moe_ops.sigmoid_topk_route(
            logits, zoo.centred(bias), cfg.num_experts_per_tok, cfg.route_scale)
        return h, weights, idx

    return [(zoo, "route", route)]


def _residual_stream_in_bfloat16(zoo, moe_ops, jnp, jax):
    """The residual stream written in bfloat16 after each sub-block, as an
    implementation that keeps its activations in bfloat16 holds it."""
    def block(p, x, bias, kind, cfg):
        y, gate_mean = zoo.attention(p, x, kind, cfg)
        x = _rounded(x + zoo.post_attn_norm(p, y, cfg), jax)
        if bias is None:
            y, stats = zoo.dense_mlp(p, x, cfg), None
        else:
            y, stats = zoo.moe(p, x, bias, cfg)
        return _rounded(x + zoo.post_mlp_norm(p, y, cfg), jax), gate_mean, stats

    return [(zoo, "block", block)]


def _attention_activations_in_bfloat16(zoo, moe_ops, jnp, jax):
    """Everything the attention block states float32 between its matmuls
    written in bfloat16, as an implementation that keeps its activations in
    bfloat16 holds them: q, k and the gate's logits as their projections write
    them, the head norms' output (what the rotation reads), the sigmoid and
    its product with the kernels' output."""
    plain_norm = zoo.qk_norm
    r = lambda x: _rounded(x, jax)

    def qk_norm(p, q, k, cfg):
        q, k = plain_norm(p, r(q), r(k), cfg)
        return r(q), r(k)

    def gate(p, h, out, cfg):
        g = r(jax.nn.sigmoid(r(zoo.matmul(
            h, p["wg"], jnp.dtype(cfg.compute_dtype), jnp.float32))))
        return r(out.astype(jnp.float32) * g), jnp.mean(g, axis=(1, 2))

    return [(zoo, "qk_norm", qk_norm), (zoo, "gate", gate)]


def _bias_update(times: float):
    """The routers' selection bias moved by `times` its update after a step:
    0 leaves it where it was, −1 moves it the wrong way."""
    def patch(zoo, moe_ops, jnp, jax):
        plain = zoo.updated_bias
        return [(zoo, "updated_bias", lambda bias, idx, cfg: (
            bias + times * (plain(bias, idx, cfg) - bias)))]
    return patch


def _nemotron(patch):
    """A patch of `departures_nemotron_h.py`'s signature (zoo, moe_ops, ssm,
    jnp, jax) under this file's."""
    return lambda zoo, moe_ops, jnp, jax: patch(zoo, moe_ops, None, jnp, jax)


# the nearest precision below the stated one, where the statement is float32:
# the router's scores, the residual stream, the attention block's activations
CONTROLS = {
    "a_bfloat16_router": _bf16_router,
    "residual_stream_in_bfloat16": _residual_stream_in_bfloat16,
    "attention_activations_in_bfloat16": _attention_activations_in_bfloat16,
}
# what reads `correct: true` on the chip at full width (my chip runs, PR 44,
# seeds 2147484601 and 2147485001; PERF.md §6): one key more or fewer of a
# window's 2048 moves no figure by more than 1.08 times what the seeds do (at
# seeded weights a key carries 1/2048 of a softmax, 5e-4, under bfloat16's
# 4e-3); the residual stream rounded to bfloat16 moves the router's first
# moment by 1.42 times and nothing else by more than 1.32 (every reader of the
# stream is a norm and then a matmul that rounds its operand to bfloat16
# anyway); the attention block's activations in bfloat16 move nothing by more
# than 1.1 times (q and k enter the kernels, the gated output `W_o`, as
# bfloat16 in any case)
BELOW_THE_NOISE_ON_THE_CHIP = {
    "window_one_key_short", "window_one_key_long", "residual_stream_in_bfloat16",
    "attention_activations_in_bfloat16"}
DEPARTURES = {
    "gate_left_out": _gate("left_out"),
    "gate_after_the_output_projection": _gate("after_wo"),
    "gate_from_the_unnormed_input": _gate("from_the_stream"),
    "rotation_in_the_full_layers": _positions(("sliding", "full")),
    "no_rotation_in_the_sliding_layers": _positions(()),
    "qk_norm_left_out": _qk_norm_left_out,
    "qk_norm_after_the_rotation": _qk_norm_after_the_rotation,
    "post_attn_norm_left_out": _post_norm_left_out("post_attn_norm"),
    "post_mlp_norm_left_out": _post_norm_left_out("post_mlp_norm"),
    "embedding_multiplier_left_out": _embedding_multiplier_left_out,
    "route_scale_left_out": _nemotron(_route_with(
        lambda jnp, chosen, bias, scale: _renormalised(jnp, chosen, 1.0))),
    "weights_not_renormalised": _nemotron(_route_with(
        lambda jnp, chosen, bias, scale: scale * chosen)),
    "bias_used_as_a_weight": _nemotron(_route_with(
        lambda jnp, chosen, bias, scale: _renormalised(jnp, chosen + bias, scale))),
    "window_one_key_short": _window(-1),
    "window_one_key_long": _window(+1),
    "shared_expert_left_out": _shared_expert_left_out,
    "bias_update_left_out": _bias_update(0.0),
    "bias_update_mis_signed": _bias_update(-1.0),
}
ALL = {**DEPARTURES, **CONTROLS}


@contextlib.contextmanager
def applied(name, zoo):
    """The program with departure `name` patched in (None: as it is)."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import moe as moe_ops

    patches = ALL[name](zoo, moe_ops, jnp, jax) if name else []
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    for obj, attr, new in patches:
        setattr(obj, attr, new)
    try:
        yield
    finally:
        for obj, attr, old in saved:
            setattr(obj, attr, old)


def held_shares(idx, num_experts: int, shares: int):
    """(layers, N, k) expert ids -> (layers, shares): the share of a layer's
    pairs that each of `shares` equal ranges of the experts receives."""
    import numpy as np

    idx = np.asarray(idx)
    counts = np.stack([np.bincount(layer.ravel(), minlength=num_experts) for layer in idx])
    return counts.reshape(idx.shape[0], shares, -1).sum(-1) / idx[0].size


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="trinity-mini.resident-16k")
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--seeds", default="",
                    help="run the program AS IT IS at each of these seeds and print "
                         "every figure: what the tolerances are derived from")
    ap.add_argument("--only", default="")
    ap.add_argument("--check_steps", type=int, default=0)
    ap.add_argument("--warmup_steps", type=int, default=0,
                    help="the optimizer's warm-up, program and reference alike: 1 reads "
                         "the update's figures at the full step size")
    ap.add_argument("--held_share", action="store_true",
                    help="print the eight shares' part of every layer's pairs after "
                         "the settling, and run no check")
    args = ap.parse_args(argv)

    if args.seeds:
        return sum(main(["--workload", args.workload, "--seed", seed, "--only", "none",
                         "--check_steps", str(args.check_steps),
                         "--warmup_steps", str(args.warmup_steps)])
                   for seed in args.seeds.split(","))
    resolved = common.resolve_cell(args.workload)
    config, traffic = resolved["config"], resolved["traffic"]
    if args.warmup_steps:
        config["model_params"] = common.format_model_params(
            {**common.model_params(config), "warmup_steps": str(args.warmup_steps)})
    driver = common.load_module("drivers", traffic["driver"])
    model_params = common.model_params(config)
    reference = common.load_module("reference", common.model_name(config))
    steps = args.check_steps or int(traffic["check_steps"])
    batch = int(traffic["batch_per_chip"])
    tokens = driver._lm.tokens_from_seed(
        args.seed, steps * batch, int(traffic["seq_len"]),
        int(model_params["vocab_size"]), float(traffic["zipf_s"]))
    batches = driver._lm._batches(tokens, batch, 0, steps)
    names = [None if n == "none" else n for n in args.only.split(",") if n] \
        or [None] + sorted(CONTROLS) + sorted(DEPARTURES)
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
    fresh = None
    wrong = 0
    for name in names:
        # a new trainer every time: the patched functions must be traced anew
        spec, mesh, trainer, zoo = fresh_trainer(driver, config, args.seed)
        if fresh is None:
            # the selection bias as the cell settles it, by the program AS IT
            # IS, once a seed: every departure starts from the same state
            fresh = driver.settled_state_maker(
                trainer, zoo, spec, reference, batches, settle, held, lambda text: None)
        with applied(name, zoo):
            verdict = driver.program_check(
                trainer, spec, mesh, zoo, reference, model_params, batches,
                fresh, lambda text: None)
        expected = name is None or name in BELOW_THE_NOISE_ON_THE_CHIP
        wrong += verdict["ok"] != expected
        print(f"seed {args.seed} {name or 'the program as it is'}: correct: "
              f"{'true' if verdict['ok'] else 'false'}"
              f"{'' if verdict['ok'] == expected else '  <-- UNEXPECTED'}; "
              f"failures: {verdict['failures']}; figures: {verdict['figures']}", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    raise SystemExit(main())
