"""The ten departures the Nemotron-H cell's check must catch and the precision
controls its limits are read against (what the configuration states float32,
computed or kept in bfloat16: `CONTROLS`), each as a patch of the PROGRAM (the
zoo module and the operations it calls), and a command that runs the cell's
check — the driver's own `program_check` — under each of them on the chip at
full width:

    chiprun --chips 1 --timeout 3000 -- python3 benchmark/rehearse/departures_nemotron_h.py \
        [--seed N] [--only name,name] [--check_steps 2]

Every line it prints holds `correct: true|false`, the failures and every
figure of the comparison. The unpatched program must read true, every
departure and every control false; `BELOW_THE_NOISE` names what reads true. The CPU tests
(`tests/test_nemotron_h.py`) apply the same patches at the tiny preset. None
of this is run by the benchmark; nothing here is an option of the program.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402


def _bf16_router(zoo, moe_ops, ssm, jnp, jax):
    def route(p, x, bias, cfg):
        h = zoo.rmsnorm(x, p["moe_norm"], cfg.layer_norm_epsilon).reshape(-1, x.shape[-1])
        logits = jnp.dot(h.astype(jnp.bfloat16),
                         p["moe_router"].astype(jnp.bfloat16)).astype(jnp.float32)
        _, weights, idx = moe_ops.sigmoid_topk_route(
            logits, bias, cfg.num_experts_per_tok, cfg.routed_scaling_factor)
        return h, weights, idx

    return [(zoo, "route", route)]


def _route_with(change):
    """A router whose (scores, chosen scores, bias of the chosen, scale) ->
    weights rule is `change`'s."""

    def patch(zoo, moe_ops, ssm, jnp, jax):
        def sigmoid_topk_route(logits, bias, k, scale):
            scores = jax.nn.sigmoid(logits.astype(jnp.float32))
            _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
            chosen = jnp.take_along_axis(scores, idx, axis=-1)
            chosen_bias = jnp.take_along_axis(
                jnp.broadcast_to(bias.astype(jnp.float32), scores.shape), idx, axis=-1)
            return scores, change(jnp, chosen, chosen_bias, scale), idx

        return [(moe_ops, "sigmoid_topk_route", sigmoid_topk_route)]

    return patch


def _renormalised(jnp, chosen, scale):
    return scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def _no_d_term(zoo, moe_ops, ssm, jnp, jax):
    plain = ssm.ssd_chunked

    def without_dx(xs, delta, a, b, c, chunk, dt):
        # the mixer adds D·x to what this returns; take it away again
        return plain(xs, delta, a, b, c, chunk, dt) - xs.astype(jnp.float32)

    return [(ssm, "ssd_chunked", without_dx)]            # D is ones at the seed


def _no_conv_bias(zoo, moe_ops, ssm, jnp, jax):
    plain = ssm.causal_conv1d
    return [(ssm, "causal_conv1d", lambda x, w, b: plain(x, w, jnp.zeros_like(b)))]


def _no_gate(zoo, moe_ops, ssm, jnp, jax):
    def ungated(y, z, weight, groups, eps):
        y = y.astype(jnp.float32)
        shape = y.shape
        g = y.reshape(shape[:-1] + (groups, shape[-1] // groups))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        return g.reshape(shape) * weight

    return [(ssm, "gated_group_rmsnorm", ungated)]


def _one_group_norm(zoo, moe_ops, ssm, jnp, jax):
    plain = ssm.gated_group_rmsnorm
    return [(ssm, "gated_group_rmsnorm",
             lambda y, z, weight, groups, eps: plain(y, z, weight, 1, eps))]


def _rotary(zoo, moe_ops, ssm, jnp, jax):
    plain = zoo.full_attention

    def rope(x, theta=10000.0):
        t, d = x.shape[1], x.shape[-1]
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
        cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
        sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        xf = x.astype(jnp.float32)
        return (xf * cos + jnp.concatenate([-x2, x1], axis=-1).astype(jnp.float32) * sin
                ).astype(x.dtype)

    return [(zoo, "full_attention",
             lambda q, k, v, causal=True: plain(rope(q), rope(k), v, causal=causal))]


def _kv_head_modulo(zoo, moe_ops, ssm, jnp, jax):
    plain = zoo.full_attention

    def interleaved(q, k, v, causal=True):
        # query head i with key-value head i % Hkv: regroup the query heads
        # so that the kernel's i // group reads that head, and put them back
        b, t, h, d = q.shape
        kv = k.shape[2]
        to = lambda x: x.reshape(b, t, h // kv, kv, d).swapaxes(2, 3).reshape(b, t, h, d)
        back = lambda x: x.reshape(b, t, kv, h // kv, d).swapaxes(2, 3).reshape(b, t, h, d)
        return back(plain(to(q), k, v, causal=causal))

    return [(zoo, "full_attention", interleaved)]


def _rounded(x, jax):
    """x at bfloat16's eight bits, still float32. An explicit
    `reduce_precision`: a cast to bfloat16 and back is a pair XLA is free to
    drop (`xla_allow_excess_precision`), and on the chip it does."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _delta_in_bfloat16(zoo, moe_ops, ssm, jnp, jax):
    """Δ rounded to bfloat16 before the scan: the decays exp(Δ·A) and every
    cumulative sum of Δ·A start from eight bits."""
    plain = ssm.ssd_chunked
    return [(ssm, "ssd_chunked", lambda xs, delta, a, b, c, chunk, dt:
             plain(xs, _rounded(delta, jax), a, b, c, chunk, dt))]


def _ssm_kept_in_bfloat16(zoo, moe_ops, ssm, jnp, jax):
    """What the configuration states float32 in the state-space path, kept in
    bfloat16 between its operations as a bfloat16 implementation keeps it: the
    convolution's operands and output, Δ, the scan's output, the gated norm's
    operands and output."""
    conv, scan, norm = ssm.causal_conv1d, ssm.ssd_chunked, ssm.gated_group_rmsnorm
    r = lambda x: _rounded(x, jax)
    return [
        (ssm, "causal_conv1d", lambda x, w, b: r(conv(r(x), r(w), r(b)))),
        (ssm, "ssd_chunked", lambda xs, delta, a, b, c, chunk, dt:
            r(scan(xs, r(delta), a, b, c, chunk, dt))),
        (ssm, "gated_group_rmsnorm", lambda y, z, weight, groups, eps:
            r(norm(r(y), r(z), weight, groups, eps)))]


# the nearest precision below the stated one, where the statement is float32
# (the router's is among the departures)
CONTROLS = {
    "ssm_kept_in_bfloat16": _ssm_kept_in_bfloat16,
}
# a control the check reads `correct: true` on the chip: Δ alone in bfloat16
# moves no figure by more than the seeds do (PERF.md §6, PR 30); kept for a
# check that can see it
BELOW_THE_NOISE = {
    "delta_in_bfloat16": _delta_in_bfloat16,
}

DEPARTURES = {
    "a_bfloat16_router": _bf16_router,
    "weights_not_renormalised": _route_with(
        lambda jnp, chosen, bias, scale: scale * chosen),
    "scaling_factor_left_out": _route_with(
        lambda jnp, chosen, bias, scale: _renormalised(jnp, chosen, 1.0)),
    "bias_used_as_a_weight": _route_with(
        lambda jnp, chosen, bias, scale: _renormalised(jnp, chosen + bias, scale)),
    "d_x_left_out": _no_d_term,
    "conv_bias_left_out": _no_conv_bias,
    "silu_z_left_out": _no_gate,
    "one_rms_over_all_channels": _one_group_norm,
    "rotary_applied": _rotary,
    "kv_head_i_mod_2": _kv_head_modulo,
}


@contextlib.contextmanager
def applied(name, zoo):
    """The program with departure `name` patched in (None: as it is)."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import moe as moe_ops
    from elasticdl_tpu.ops import ssm

    patches = ({**DEPARTURES, **CONTROLS, **BELOW_THE_NOISE}[name](zoo, moe_ops, ssm, jnp, jax)
               if name else [])
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    for obj, attr, new in patches:
        setattr(obj, attr, new)
    try:
        yield
    finally:
        for obj, attr, old in saved:
            setattr(obj, attr, old)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="nemotron-3-nano-30b-a3b.resident-8k")
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--seeds", default="",
                    help="run the program AS IT IS at each of these seeds and print "
                         "every figure: what the tolerances are derived from")
    ap.add_argument("--only", default="")
    ap.add_argument("--check_steps", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    if args.seeds:
        wrong = 0
        for seed in args.seeds.split(","):
            wrong += main(["--workload", args.workload, "--seed", seed, "--only", "none",
                           "--check_steps", str(args.check_steps)])
        return wrong
    resolved = common.resolve_cell(args.workload)
    config, traffic = resolved["config"], resolved["traffic"]
    driver = common.load_module("drivers", traffic["driver"])
    model_params = common.model_params(config)
    reference = common.load_module("reference", common.model_name(config))
    steps = args.check_steps or int(traffic["check_steps"])
    tokens = driver._lm.tokens_from_seed(
        args.seed, steps * int(traffic["batch_per_chip"]), int(traffic["seq_len"]), int(model_params["vocab_size"]),
        float(traffic["zipf_s"]))
    batch = int(traffic["batch_per_chip"])
    batches = driver._lm._batches(tokens, batch, 0, steps)
    names = [None if n == "none" else n for n in args.only.split(",") if n] \
        or [None] + sorted(BELOW_THE_NOISE) + sorted(CONTROLS) + sorted(DEPARTURES)
    wrong = 0
    for name in names:
        # a new trainer every time: the patched functions must be traced anew
        cfg, spec, mesh, trainer = driver._resident.build_trainer(
            config, jax.devices()[:1], args.seed)
        zoo = sys.modules[spec.module_name]

        def fresh_state():
            state = trainer.init_state(batches[0])
            jax.block_until_ready(state.params)
            return state

        with applied(name, zoo):
            verdict = driver.program_check(
                trainer, spec, mesh, zoo, reference, model_params, batches,
                fresh_state, lambda text: None)
        expected = name is None or name in BELOW_THE_NOISE
        wrong += verdict["ok"] != expected
        print(f"seed {args.seed} {name or 'the program as it is'}: correct: "
              f"{'true' if verdict['ok'] else 'false'}"
              f"{'' if verdict['ok'] == expected else '  <-- UNEXPECTED'}; "
              f"failures: {verdict['failures']}; figures: {verdict['figures']}", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    raise SystemExit(main())
