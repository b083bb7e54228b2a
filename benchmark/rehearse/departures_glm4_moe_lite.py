"""The departures the GLM-4.7-Flash cell's check must catch and the precision
control its limits are read against (`CONTROLS`: what the configuration states
float32, kept in bfloat16), each as a patch of the PROGRAM (the zoo module and
the operations it calls), and a command that runs the cell's check — the
driver's own `program_check` — under each of them on the chip at full width:

    chiprun --chips 1 --timeout 3000 -- python3 benchmark/rehearse/departures_glm4_moe_lite.py \
        [--seed N] [--only name,name] [--seeds a,b,c] [--check_steps 2]

Every line it prints holds `correct: true|false`, the failures and every
figure of the comparison. The unpatched program must read true, every
departure and every control false; `BELOW_THE_NOISE` names what this check
cannot see at seeded weights (it reads true, and says so). The CPU tests
(`tests/test_glm4_moe_lite.py`) apply the same patches at the tiny preset.
None of this is run by the benchmark; nothing here is an option of the
program. The routers' patches are `departures_nemotron_h.py`'s (one router).
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402

_nemotron = common.load_module("rehearse", "departures_nemotron_h")
_route_with, _renormalised, _rounded = (
    _nemotron._route_with, _nemotron._renormalised, _nemotron._rounded)


def _inside(zoo, outer: str, attr: str, replacement):
    """A patch of `zoo.<outer>(p, x, ..., cfg)` that runs it with `zoo.<attr>`
    replaced by `replacement(plain, cfg)`: a change to ONE use of a function
    the module calls in many places."""
    plain_outer = getattr(zoo, outer)

    def patched(*args):
        plain = getattr(zoo, attr)
        setattr(zoo, attr, replacement(plain, args[-1]))
        try:
            return plain_outer(*args)
        finally:
            setattr(zoo, attr, plain)

    return [(zoo, outer, patched)]


def _latent_norms_skipped(zoo, moe_ops, ssm, jnp, jax):
    """c_q and c_kv go on as the down-projections left them: every RMSNorm
    whose input is not as wide as the residual stream is skipped (its weight,
    ones at the seed, still multiplies)."""
    return _inside(zoo, "latent_attention", "rmsnorm", lambda plain, cfg: (
        lambda x, w, eps: plain(x, w, eps) if x.shape[-1] == cfg.hidden_size
        else x.astype(jnp.float32) * w))


def _scale(width_of):
    """Softmax at `width_of(cfg)`^-1/2 in place of (nope + rope)^-1/2: the
    queries are stretched before the kernel, which scales by its head size."""
    def patch(zoo, moe_ops, ssm, jnp, jax):
        return _inside(zoo, "latent_attention", "full_attention", lambda plain, cfg: (
            lambda q, k, v, causal=True: plain(
                (q.astype(jnp.float32) * (q.shape[-1] / width_of(cfg)) ** 0.5).astype(q.dtype),
                k, v, causal=causal)))
    return patch


def _no_rotary_on_the_shared_key(zoo, moe_ops, ssm, jnp, jax):
    plain = zoo.rope
    return [(zoo, "rope", lambda x, theta: x if x.shape[2] == 1 else plain(x, theta))]


def _shared_expert_dropped(zoo, moe_ops, ssm, jnp, jax):
    # inside `moe` the gated unit is the shared expert's alone
    return _inside(zoo, "moe", "gated_mlp", lambda plain, cfg: (
        lambda h, *w: jnp.zeros(h.shape, jnp.float32)))


def _mtp_target_off_by_one(zoo, moe_ops, ssm, jnp, jax):
    return [(zoo, "mtp_labels", lambda labels: labels)]      # the NEXT token again


def _mtp_weight_zero(zoo, moe_ops, ssm, jnp, jax):
    return [(zoo, "MTP_LOSS_WEIGHT", 0.0)]


def _mtp_head_of_its_own(zoo, moe_ops, ssm, jnp, jax):
    """The module's logits from another matrix than the main stream's head
    (its columns shifted by one id, no gradient to the head): of the two
    `_head` calls of a forward pass the second is the module's."""
    plain_forward, plain_head = zoo.forward, zoo._head
    calls = [0]

    def forward(*args):
        calls[0] = 0
        return plain_forward(*args)

    def head(x, norm, matrix, cfg):
        calls[0] += 1
        if calls[0] == 2:
            matrix = jax.lax.stop_gradient(jnp.roll(matrix, 1, axis=1))
        return plain_head(x, norm, matrix, cfg)

    return [(zoo, "forward", forward), (zoo, "_head", head)]


def _bf16_router(zoo, moe_ops, ssm, jnp, jax):
    def route(p, x, bias, cfg):
        h = zoo.rmsnorm(x, p["moe_norm"], cfg.rms_norm_eps).reshape(-1, x.shape[-1])
        logits = jnp.dot(h.astype(jnp.bfloat16),
                         p["moe_router"].astype(jnp.bfloat16)).astype(jnp.float32)
        _, weights, idx = moe_ops.sigmoid_topk_route(
            logits, bias, cfg.num_experts_per_tok, cfg.routed_scaling_factor)
        return h, weights, idx

    return [(zoo, "route", route)]


def _latents_kept_in_bfloat16(zoo, moe_ops, ssm, jnp, jax):
    """The low-rank down-projections written in bfloat16, as an implementation
    that keeps its activations in bfloat16 writes them: c_q, c_kv and the
    rotary key reach their RMSNorms and the rotation at eight bits."""
    def rounding(plain, cfg):
        narrow = (cfg.q_lora_rank, cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        return lambda x, w, dt, out=None: (
            _rounded(plain(x, w, dt, out), jax) if w.shape[-1] in narrow
            else plain(x, w, dt, out))

    return _inside(zoo, "latent_attention", "_matmul", rounding)


def _sub_block_outputs_in_bfloat16(zoo, moe_ops, ssm, jnp, jax):
    """What a sub-block adds to the residual stream written in bfloat16, as an
    implementation that keeps its activations in bfloat16 writes it: the
    stream every later norm and router reads is then eight bits wide in each
    of its terms."""
    attention, mlp, moe = zoo.latent_attention, zoo.dense_mlp, zoo.moe

    def rounded_moe(p, x, bias, cfg):
        y, stats = moe(p, x, bias, cfg)
        return _rounded(y, jax), stats

    return [(zoo, "latent_attention", lambda p, x, cfg: _rounded(attention(p, x, cfg), jax)),
            (zoo, "dense_mlp", lambda p, x, cfg: _rounded(mlp(p, x, cfg), jax)),
            (zoo, "moe", rounded_moe)]


# the nearest precision below the stated one, where the statement is float32:
# the router's scores and the residual stream
CONTROLS = {
    "a_bfloat16_router": _bf16_router,
    "sub_block_outputs_in_bfloat16": _sub_block_outputs_in_bfloat16,
}
# what the check reads `correct: true` on the chip, kept for a check that can
# see it: c_q, c_kv and the rotary key rounded to bfloat16 before their norms
# move no figure by more than 4% (the next matmul rounds its operand to
# bfloat16 anyway; PERF.md §6, PR 32)
BELOW_THE_NOISE = {
    "latents_kept_in_bfloat16": _latents_kept_in_bfloat16,
}

DEPARTURES = {
    "latent_norms_skipped": _latent_norms_skipped,
    "scale_by_the_nope_width": _scale(lambda cfg: cfg.qk_nope_head_dim),
    "scale_by_half_the_head": _scale(
        lambda cfg: (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) / 2),
    "no_rotary_on_the_shared_key": _no_rotary_on_the_shared_key,
    "bias_used_as_a_weight": _route_with(
        lambda jnp, chosen, bias, scale: _renormalised(jnp, chosen + bias, scale)),
    "scaling_factor_left_out": _route_with(
        lambda jnp, chosen, bias, scale: _renormalised(jnp, chosen, 1.0)),
    "shared_expert_dropped": _shared_expert_dropped,
    "mtp_target_off_by_one": _mtp_target_off_by_one,
    "mtp_weight_zero": _mtp_weight_zero,
    "mtp_head_of_its_own": _mtp_head_of_its_own,
}


@contextlib.contextmanager
def applied(name, zoo):
    """The program with departure `name` patched in (None: as it is)."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import moe as moe_ops

    patches = ({**DEPARTURES, **CONTROLS, **BELOW_THE_NOISE}[name](
        zoo, moe_ops, None, jnp, jax) if name else [])
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    for obj, attr, new in patches:
        setattr(obj, attr, new)
    try:
        yield
    finally:
        for obj, attr, old in saved:
            setattr(obj, attr, old)


def fresh_trainer(driver, config, seed: int):
    """(spec, mesh, trainer, zoo) with a program token of the trainer's own:
    under the job's token the process-wide cache of compiled steps
    (`training/compile_cache.py`: what a rebuilt trainer of the same job gets
    back) would hand every variant the FIRST one's step, compiled from the
    unpatched functions."""
    import jax

    from elasticdl_tpu.training import compile_cache

    _, spec, mesh, trainer = driver._resident.build_trainer(config, jax.devices()[:1], seed)
    trainer.cache_token = compile_cache.instance_token()
    return spec, mesh, trainer, sys.modules[spec.module_name]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="glm-4.7-flash.resident-8k")
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--seeds", default="",
                    help="run the program AS IT IS at each of these seeds and print "
                         "every figure: what the tolerances are derived from")
    ap.add_argument("--only", default="")
    ap.add_argument("--check_steps", type=int, default=0)
    args = ap.parse_args(argv)

    if args.seeds:
        return sum(main(["--workload", args.workload, "--seed", seed, "--only", "none",
                         "--check_steps", str(args.check_steps)])
                   for seed in args.seeds.split(","))
    resolved = common.resolve_cell(args.workload)
    config, traffic = resolved["config"], resolved["traffic"]
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
        or [None] + sorted(BELOW_THE_NOISE) + sorted(CONTROLS) + sorted(DEPARTURES)
    hp = reference.hyper(model_params)
    fresh = None
    wrong = 0
    for name in names:
        # a new trainer every time: the patched functions must be traced anew
        spec, mesh, trainer, zoo = fresh_trainer(driver, config, args.seed)
        if fresh is None:
            # the selection bias as the cell settles it, by the program AS IT
            # IS, once a seed: every departure starts from the same state
            fresh = driver.settled_state_maker(
                trainer, zoo, spec, reference, batches,
                int(traffic["settle_router_steps"]),
                (hp["first_expert"], hp["n_routed_experts"]), lambda text: None)
        fresh_state = fresh

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
