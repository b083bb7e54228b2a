"""The departures the Kimi Linear cell's check must catch and the precision
controls its limits are read against (`CONTROLS`: what the configuration states
float32, kept in bfloat16), each as a patch of the PROGRAM (the zoo module and
the modules it calls), and a command that runs the cell's check — the driver's
own `program_check` — under each of them on the chip at full width:

    chiprun --chips 1 --timeout 3300 -- python3 benchmark/rehearse/departures_kimi_linear.py \
        [--seeds a,b,c] [--only none,name,name] [--check_steps 2] [--seq_len 8192] [--held_share]

(every case named, `none` the program as it is, at every seed, in one process.)
Every line it prints holds `correct: true|false`, the failures and every figure
of the comparison. The unpatched program must read true, every departure and
every control false, but for `BELOW_THE_NOISE`: what this check cannot see at
full width on seeded weights (it reads true there, and says so; the CPU tests,
in float32, catch each). THE FIRST TWO GUARD WHAT THIS MODEL ADDS and must
fail: a check that cannot tell a decay a channel from one a head (what
`ops/ssm.py::ssd_chunked` could have run) or a delta rule from a gated linear
attention (the erase term left out) does not hold the mixer. `--held_share`
prints, after the settling, the share of every sparse layer's pairs that each
of the 32 shares of 8 experts receives (the configuration's
`assumed.held_share`) and runs no check. The CPU tests
(`tests/test_kimi_linear_check.py`) apply the same patches at the tiny preset.
None of this is run by the benchmark; nothing here is an option of the
program. THE CELL'S PROGRAM FILLS THE CHIP TO 98%: at 16 384 tokens a patched
program that holds one more (T, 4096) plane is refused (the scalar decay:
16.56 G of 15.75 G; PR 54's first departures call), and keeping less across a
layer's recomputation needs MORE, not less (18.47 G: the second call). So
`--seq_len` reads the cases at a shorter sequence, the unpatched program beside
them at the same length; a case the compiler still refuses is reported `DID
NOT RUN` and the others go on.
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
# a write strength so small that β k kᵀ vanishes beside I, with v scaled to
# keep β v: the recurrence without its erase term, by the operator itself
_NO_ERASE = 1e-4


def _scalar_decay_a_head(zoo, delta_rule, jnp, jax):
    """One decay a head: the mean of g over the head's channels."""
    plain = zoo.log_decay

    def log_decay(p, a, heads):
        g = plain(p, a, heads)
        return jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)

    return [(zoo, "log_decay", log_decay)]


def _recurrence_with(change):
    """`change(q, k, v, g, beta) -> the same five`, before the recurrence."""
    def patch(zoo, delta_rule, jnp, jax):
        plain = zoo.recurrence
        return [(zoo, "recurrence",
                 lambda q, k, v, g, beta, cfg: plain(*change(jnp, q, k, v, g, beta), cfg))]
    return patch


def _k_l2_norm_left_out(zoo, delta_rule, jnp, jax):
    plain = zoo.qk_normalised
    return [(zoo, "qk_normalised", lambda q, k: (plain(q, k)[0], k))]


def _output_gate_left_out(zoo, delta_rule, jnp, jax):
    plain = zoo.gated_output
    # sigmoid(z) = 1 at z = +inf; 30 is there in float32
    return [(zoo, "gated_output",
             lambda p, o, z, cfg: plain(p, o, jnp.full_like(z, 30.0), cfg))]


def _rotary_in_the_latent_layer(zoo, delta_rule, jnp, jax):
    """The plain rotary table at theta 10 000 (the config's `rope_theta`, which
    `mla_use_nope` leaves unused) on q's last 64 channels and on k_r."""
    return [(zoo, "no_positions", lambda part: zoo.glm.rope(part, 10000.0))]


def _k_r_left_out(zoo, delta_rule, jnp, jax):
    """The ONE shared key head (B, T, 1, 64) zeroed: the scores are the
    per-head part alone."""
    return [(zoo, "no_positions",
             lambda part: part * 0.0 if part.shape[2] == 1 else part)]


def _glm_low_rank_query(zoo, delta_rule, jnp, jax):
    """GLM's query put in from the parameters the model has: c_q =
    rmsnorm(h W_q[:, :768]), q = c_q W_q[:768, :] — rank 768 (less at a tiny size) and a query norm
    where the configuration has neither (`q_lora_rank` null)."""
    glm = zoo.glm
    plain = glm.latent_attention

    def attention(p, x, cfg, **more):
        w = p["q_proj"]
        rank = min(768, *w.shape)
        return plain({**p, "q_a": w[:, :rank], "q_a_norm": jnp.ones((rank,)),
                      "q_b": w[:rank, :]}, x, cfg, **more)

    return [(glm, "latent_attention", attention)]


def _cumulative_decay_in_bfloat16(zoo, delta_rule, jnp, jax):
    plain = delta_rule.cumulative_log_decay
    return [(delta_rule, "cumulative_log_decay", lambda g: _rounded(plain(g), jax))]


def _state_in_bfloat16(zoo, delta_rule, jnp, jax):
    plain = delta_rule.next_state
    return [(delta_rule, "next_state", lambda *args: _rounded(plain(*args), jax))]


# the nearest precision below the stated one, where the statement is float32
# and the chip's check can see it: `mu_rel_l2.kda_f_a` / `kda_f_b` 0.0231 /
# 0.0229 against 0.0074 sound (8192 tokens, seed 2147483777; PERF.md §6)
CONTROLS = {
    "cumulative_decay_in_bfloat16": _cumulative_decay_in_bfloat16,
}
# what the check reads like the program as it is on the chip: the state a
# chunk leaves rounded to bfloat16 moves no figure (the KDA leaves'
# `mu_rel_l2` 0.0058–0.0076 against 0.0055–0.0075 unpatched, same seed and
# length) — every product that reads the state rounds it to bfloat16 anyway,
# so the carried float32 state is held by the CPU tests alone (PERF.md §7)
BELOW_THE_NOISE = {
    "state_in_bfloat16": _state_in_bfloat16,
}
DEPARTURES = {
    "scalar_decay_a_head": _scalar_decay_a_head,
    "erase_term_left_out": _recurrence_with(
        lambda jnp, q, k, v, g, beta: (q, k, v * beta[..., None] / _NO_ERASE, g,
                                       jnp.full_like(beta, _NO_ERASE))),
    "beta_doubled": _recurrence_with(lambda jnp, q, k, v, g, beta: (q, k, v, g, 2.0 * beta)),
    "k_l2_norm_left_out": _k_l2_norm_left_out,
    "output_gate_left_out": _output_gate_left_out,
    "rotary_in_the_latent_layer": _rotary_in_the_latent_layer,
    "k_r_left_out_of_the_scores": _k_r_left_out,
    "glm_low_rank_query_put_in": _glm_low_rank_query,
}
MUST_FAIL = ("scalar_decay_a_head", "erase_term_left_out")


@contextlib.contextmanager
def applied(name, zoo):
    """The program with departure `name` patched in (None: as it is)."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import delta_rule

    patches = ({**DEPARTURES, **CONTROLS, **BELOW_THE_NOISE}[name](zoo, delta_rule, jnp, jax)
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
    ap.add_argument("--workload", default="kimi-linear-48b-a3b.resident-16k")
    ap.add_argument("--seeds", default="2147483777",
                    help="every case of --only at each of these seeds")
    ap.add_argument("--only", default="",
                    help="`none` is the program as it is; default: every case")
    ap.add_argument("--check_steps", type=int, default=0)
    ap.add_argument("--seq_len", type=int, default=0,
                    help="the cases at this many tokens a sequence (default: the cell's)")
    ap.add_argument("--held_share", action="store_true",
                    help="print the 32 shares' part of every layer's pairs after the "
                         "settling, and run no check")
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
    held = (hp["first_expert"], hp["n_routed_experts"])
    settle = int(traffic["settle_router_steps"])
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
            state = driver.settled_state_maker(
                trainer, zoo, spec, reference, batches, settle, held, print)()
            bias = driver._share._get_path(state.extra_vars, reference.BIAS)
            idx = jax.device_get(driver._assignments(zoo, spec)(
                state.params, bias, batches[0]["features"])[0])
            shares = hp["num_experts"] // hp["n_routed_experts"]
            print(f"seed {seed}, after {settle} settling passes: the share of each sparse "
                  f"layer's pairs on each of the {shares} shares of {hp['n_routed_experts']} "
                  f"experts: {held_shares(idx, hp['num_experts'], shares).round(5).tolist()}",
                  flush=True)
            continue
        fresh_state = None
        for name in names:
            # a new trainer every time: the patched functions must be traced anew
            spec, mesh, trainer, zoo = fresh_trainer(driver, config, seed)
            if fresh_state is None:
                # the selection bias as the cell settles it, by the program AS
                # IT IS, once a seed: every departure starts from the same state
                fresh_state = driver.settled_state_maker(
                    trainer, zoo, spec, reference, batches, settle, held,
                    lambda text: None)
            try:
                with applied(name, zoo):
                    verdict = driver.program_check(
                        trainer, spec, mesh, zoo, reference, model_params, batches,
                        fresh_state, lambda text: None)
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
