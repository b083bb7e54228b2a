"""The departures the Qwen3-Next cell's check must catch and the precision
controls its limits are read against (`CONTROLS`: each part the configuration
states float32, kept in bfloat16 ALONE), each as a patch of the PROGRAM (the
zoo module, or the two functions `ops/delta_rule.py` looks up when a program is
traced), and a command that runs the cell's check — the driver's own
`program_check` — under each of them on the chip at full width:

    chiprun --chips 1 --timeout 3300 -- python3 benchmark/rehearse/departures_qwen3_next.py \
        [--seeds a,b,c] [--only none,name,name] [--check_steps 2] [--seq_len 8192] [--held_share]

(every case named, `none` the program as it is, at every seed, in one process.)
Every line it prints holds `correct: true|false`, the failures and every figure
of the comparison. The unpatched program must read true, every departure and
every control false, but for `BELOW_THE_NOISE`: what this check cannot see at
full width on seeded weights (it reads true there, and says so; the CPU tests,
in float32, catch each). `--held_share` prints the share of every layer's pairs
that each of the sixteen shares of 32 experts receives (the configuration's
`assumed.held_share`) and runs no check. The CPU tests
(`tests/test_qwen3_next_check.py`) apply the same patches at the tiny preset.
None of this is run by the benchmark; nothing here is an option of the
program. A case the compiler refuses (the cell's program fills the chip) is
reported `DID NOT RUN` and the others go on; `--seq_len` reads the cases at a
shorter sequence.
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
_rounded, fresh_trainer = _glm._rounded, _glm.fresh_trainer
# the same five-tuple `route`, the same patch: the chosen probabilities as they are
_weights_not_renormalised = common.load_module(
    "rehearse", "departures_mellum")._weights_not_renormalised


def _decay_in_bfloat16(zoo, jnp, jax):
    """g and its in-chunk cumulative sum Γ at bfloat16's eight bits: every
    exponential of the recurrence is then taken of a rounded number."""
    from elasticdl_tpu.ops import delta_rule

    plain = delta_rule.cumulative_log_decay
    return [(delta_rule, "cumulative_log_decay",
             lambda g: _rounded(plain(_rounded(g, jax)), jax))]


def _state_in_bfloat16(zoo, jnp, jax):
    """The state S rounded to bfloat16 every time a chunk leaves it. On the
    chip `next_state` is traced INSIDE the Pallas kernels, where Mosaic has no
    `reduce_precision` and drops no cast: the rounding is a cast to bfloat16
    and back there, `_rounded` under XLA (the plain route)."""
    from elasticdl_tpu.ops import delta_rule

    plain = delta_rule.next_state
    if jax.default_backend() == "tpu":
        rounded = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        rounded = lambda x: _rounded(x, jax)
    return [(delta_rule, "next_state", lambda through, state, added: rounded(
        plain(through, rounded(state), added)))]


def _l2_norms_in_bfloat16(zoo, jnp, jax):
    plain = zoo.qk_normalised

    def qk_normalised(q, k):
        q, k = plain(_rounded(q, jax), _rounded(k, jax))
        return _rounded(q, jax), _rounded(k, jax)

    return [(zoo, "qk_normalised", qk_normalised)]


def _head_norms_in_bfloat16(zoo, jnp, jax):
    plain = zoo.qk_norm

    def qk_norm(p, q, k, cfg):
        q, k = plain(p, _rounded(q, jax), _rounded(k, jax), cfg)
        return _rounded(q, jax), _rounded(k, jax)

    return [(zoo, "qk_norm", qk_norm)]


def _gated_norm_in_bfloat16(zoo, jnp, jax):
    plain = zoo.gated_norm
    return [(zoo, "gated_norm", lambda p, o, z, cfg: _rounded(
        plain(p, _rounded(o, jax), _rounded(z, jax), cfg), jax))]


def _bf16_router(zoo, jnp, jax):
    """The router's logits from bfloat16 operands and its softmax rounded."""
    from elasticdl_tpu.ops import moe as moe_ops

    def route(p, x, cfg):
        h = zoo.norm(x, p["moe_norm"], cfg.rms_norm_eps).reshape(-1, x.shape[-1])
        logits = jnp.dot(h.astype(jnp.bfloat16),
                         p["moe_router"].astype(jnp.bfloat16)).astype(jnp.float32)
        probs, weights, idx = moe_ops.topk_route(logits, cfg.num_experts_per_tok)
        weights = _rounded(weights, jax)
        return (h, logits, _rounded(probs, jax),
                weights / jnp.sum(weights, axis=-1, keepdims=True), idx)

    return [(zoo, "route", route)]


def _residual_stream_in_bfloat16(zoo, jnp, jax):
    """The residual stream written in bfloat16 after each sub-block, as an
    implementation that keeps its activations in bfloat16 holds it."""
    def layer(p, x, kind, cfg):
        gdn_stats = None
        if kind == "linear_attention":
            update, gdn_stats = zoo.gated_deltanet(p, x, cfg)
        else:
            update = zoo.attention(p, x, cfg)
        x = _rounded(x + update, jax)
        y, stats = zoo.moe(p, x, cfg)
        return _rounded(x + y, jax), stats, gdn_stats

    return [(zoo, "layer", layer)]


def _gate_before_the_norm(zoo, jnp, jax):
    """Mamba-2's order: the product o · silu(z) is normalised
    (`ops.ssm.gated_group_rmsnorm`), where this model normalises o and then
    gates."""
    from elasticdl_tpu.ops import ssm

    def gated_norm(p, o, z, cfg):
        flat = lambda a: a.reshape(a.shape[:2] + (-1,))
        weight = jnp.tile(p["gdn_onorm"], o.shape[2])
        return ssm.gated_group_rmsnorm(flat(o), flat(z), weight, o.shape[2],
                                       cfg.rms_norm_eps).reshape(o.shape)

    return [(zoo, "gated_norm", gated_norm)]


def _whole_head_rotated(zoo, jnp, jax):
    """Rotary positions on all `head_dim` dimensions, not on the first quarter."""
    return [(zoo, "partial_rope", lambda x, cfg: zoo.rope(x, cfg.rope_theta))]


def _shared_expert_not_gated(zoo, jnp, jax):
    def shared_expert(p, h, cfg):
        return zoo.gated_mlp(h, p["shared_gate"], p["shared_up"], p["shared_down"],
                             jnp.dtype(cfg.compute_dtype))

    return [(zoo, "shared_expert", shared_expert)]


def _value_heads_on_the_wrong_key_head(zoo, jnp, jax):
    """Value head h reads key head h mod H_k (heads interleaved) in place of
    ⌊h / r⌋: the key heads handed to the recurrence in the order that gives it."""
    plain = zoo.recurrence

    def recurrence(q, k, v, g, beta, cfg):
        hk, r = q.shape[2], v.shape[2] // q.shape[2]
        # the recurrence reads key head h // r for value head h: give value
        # head h the operands of value head (h % hk) * r + h // hk
        order = jnp.asarray([(h % hk) * r + h // hk for h in range(hk * r)])
        o, last = plain(q, k, v[:, :, order], g[:, :, order], beta[:, :, order], cfg)
        back = jnp.argsort(order)
        return o[:, :, back], last[:, back]

    return [(zoo, "recurrence", recurrence)]


# the nearest precision below the stated one, each statement broken ALONE, that
# the chip's check catches (my chip runs, PR 64, seed 2147483777; the program
# as it is beside them): g and Γ in bfloat16 moves every leaf's first moment
# five-fold and more (`embed` 0.0346 → 0.208, `gdn_dt_bias` 0.046 → 0.858) and
# the routing agreement from 0.9828 to 0.9416; a bfloat16 router reads 0.9937
# and 3.25e-3 on the same input where the program reads 1.0 and 1.15e-7
CONTROLS = {
    "decay_in_bfloat16": _decay_in_bfloat16,
    "a_bfloat16_router": _bf16_router,
}
# What the check cannot see at full width on seeded weights, with the figures
# (the same runs; sound at the same seed: `loss_rel` 1.68e-5, `mu_rel_l2.embed`
# 0.0346, `gdn_qkvz` 0.0355, the worst judged expert 0.0452, routing 0.9828):
# each reads `correct: true` there and moves the float32 program's loss at the
# tiny preset (`tests/test_qwen3_next_check.py`). Every product that reads
# these planes rounds its operands to bfloat16 anyway, and the sound readings
# of a model initialised at normal(0.02) everywhere are ten times Kimi's.
#   state_in_bfloat16            loss 1.06e-5, embed 0.0347, gdn_qkvz 0.0356, expert 0.0457
#                                (the FIRST call read it equal to the program in every
#                                bit: the kernels' `jax.jit` had kept the unpatched trace)
#   l2_norms_in_bfloat16         loss 3.96e-5, embed 0.0368, gdn_qkvz 0.0376, expert 0.0492
#   residual_stream_in_bfloat16  loss 2.57e-5, embed 0.0370, gdn_qkvz 0.0379, expert 0.0487
#   head_norms_in_bfloat16       loss 1.62e-5, embed 0.0350, gdn_qkvz 0.0358, expert 0.0464
#   gated_norm_in_bfloat16       loss 1.95e-5, embed 0.0365, gdn_qkvz 0.0374, expert 0.0440
BELOW_THE_NOISE = {
    "state_in_bfloat16": _state_in_bfloat16,
    "l2_norms_in_bfloat16": _l2_norms_in_bfloat16,
    "residual_stream_in_bfloat16": _residual_stream_in_bfloat16,
    "head_norms_in_bfloat16": _head_norms_in_bfloat16,
    "gated_norm_in_bfloat16": _gated_norm_in_bfloat16,
}

DEPARTURES = {
    "gate_before_the_norm": _gate_before_the_norm,
    "whole_head_rotated": _whole_head_rotated,
    "topk_weights_not_renormalised": _weights_not_renormalised,
    "shared_expert_not_gated": _shared_expert_not_gated,
    "value_heads_on_the_wrong_key_head": _value_heads_on_the_wrong_key_head,
}


@contextlib.contextmanager
def applied(name, zoo):
    """The program with departure `name` patched in (None: as it is)."""
    import jax
    import jax.numpy as jnp

    patches = ({**DEPARTURES, **CONTROLS, **BELOW_THE_NOISE}[name](zoo, jnp, jax)
               if name else [])
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    for obj, attr, new in patches:
        setattr(obj, attr, new)
    # the Pallas kernels are `jax.jit`s of their own: a patch of what they
    # look up when traced (`delta_rule.next_state`) is not in their cache's key
    # and would be handed the UNPATCHED trace (PR 64's first controls call read
    # `state_in_bfloat16` equal to the program as it is in every bit). A
    # patch of the zoo module needs none of it: a fresh trainer traces the zoo
    inner = any(obj is not zoo for obj, _, _ in patches)
    if inner:
        jax.clear_caches()
    try:
        yield
    finally:
        for obj, attr, old in saved:
            setattr(obj, attr, old)
        if inner:
            jax.clear_caches()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="qwen3-next-80b-a3b.resident-16k")
    ap.add_argument("--seeds", default="2147483777")
    ap.add_argument("--only", default="")
    ap.add_argument("--check_steps", type=int, default=0)
    ap.add_argument("--seq_len", type=int, default=0,
                    help="the cases at this many tokens a sequence (default: the cell's)")
    ap.add_argument("--held_share", action="store_true",
                    help="print the sixteen shares' part of every layer's pairs, and run "
                         "no check")
    args = ap.parse_args(argv)

    resolved = common.resolve_cell(args.workload)
    config, traffic = resolved["config"], resolved["traffic"]
    if args.seq_len:
        traffic["seq_len"] = args.seq_len
    driver = common.load_module("drivers", traffic["driver"])
    model_params = common.model_params(config)
    reference = common.load_module("reference", common.model_name(config))
    steps = args.check_steps or int(traffic["check_steps"])
    batch = int(traffic["batch_per_chip"])
    names = [None if n == "none" else n for n in args.only.split(",") if n] \
        or [None] + sorted(BELOW_THE_NOISE) + sorted(CONTROLS) + sorted(DEPARTURES)
    hp = reference.hyper(model_params)
    wrong = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        tokens = driver._lm.tokens_from_seed(
            seed, steps * batch, int(traffic["seq_len"]),
            int(model_params["vocab_size"]), float(traffic["zipf_s"]))
        batches = driver._lm._batches(tokens, batch, 0, steps)
        if args.held_share:
            import jax

            held_shares = common.load_module("rehearse", "departures_afmoe").held_shares
            spec, _, trainer, zoo = fresh_trainer(driver, config, seed)
            state = trainer.init_state(batches[0])
            idx = jax.device_get(driver._assignments(zoo, spec)(
                state.params, batches[0]["features"])[0])
            shares = hp["num_experts"] // hp["n_routed_experts"]
            print(f"seed {seed}: the share of each layer's pairs on each of the {shares} "
                  f"shares of {hp['n_routed_experts']} experts: "
                  f"{held_shares(idx, hp['num_experts'], shares).round(5).tolist()}",
                  flush=True)
            continue
        for name in names:
            # a new trainer every time: the patched functions must be traced anew
            spec, mesh, trainer, zoo = fresh_trainer(driver, config, seed)
            try:
                with applied(name, zoo):
                    verdict = driver.program_check(
                        trainer, spec, mesh, zoo, reference, model_params, batches,
                        lambda: trainer.init_state(batches[0]), lambda text: None)
            except Exception as error:      # a refused compile: say so, go on
                wrong += 1
                print(f"seed {seed} {name or 'the program as it is'}: DID NOT RUN: "
                      f"{type(error).__name__}: {str(error)[:300]}", flush=True)
                continue
            expected = name is None or name in BELOW_THE_NOISE
            wrong += verdict["ok"] != expected
            print(f"seed {seed} {name or 'the program as it is'}: correct: "
                  f"{'true' if verdict['ok'] else 'false'}"
                  f"{'' if verdict['ok'] == expected else '  <-- UNEXPECTED'}; "
                  f"failures: {verdict['failures']}; figures: {verdict['figures']}",
                  flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    raise SystemExit(main())
