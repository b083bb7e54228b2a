"""The departures the Mellum2 cell's check must catch and the precision
controls its limits are read against (`CONTROLS`: what the configuration
states float32, kept in bfloat16), each as a patch of the PROGRAM (the zoo
module), and a command that runs the cell's check — the driver's own
`program_check` — under each of them on the chip at full width:

    chiprun --chips 1 --timeout 3300 -- python3 benchmark/rehearse/departures_mellum.py \
        [--seed N] [--only name,name] [--seeds a,b,c] [--check_steps 2]

Every line it prints holds `correct: true|false`, the failures and every
figure of the comparison. The unpatched program must read true, every
departure and every control false. The CPU tests (`tests/test_mellum.py`)
apply the same patches at the tiny preset. None of this is run by the
benchmark; nothing here is an option of the program.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402

_glm = common.load_module("rehearse", "departures_glm4_moe_lite")
_inside, fresh_trainer = _glm._inside, _glm.fresh_trainer
_rounded = _glm._rounded


def _window(by: int):
    """The sliding layers see `sliding_window + by` keys."""
    def patch(zoo, jnp, jax):
        return _inside(zoo, "block", "attention", lambda plain, cfg: (
            lambda p, x, table, window, cfg: plain(
                p, x, table, None if window is None else window + by, cfg)))
    return patch


def _tables(choose):
    """The rotary tables as `choose(tables, zoo, cfg, seq_len)` hands them to
    the two kinds of layer."""
    def patch(zoo, jnp, jax):
        plain = zoo.rotary_tables
        return [(zoo, "rotary_tables",
                 lambda cfg, seq_len: choose(plain(cfg, seq_len), plain, cfg, seq_len))]
    return patch


def _weights_not_renormalised(zoo, jnp, jax):
    plain = zoo.route

    def route(p, x, cfg):
        h, logits, probs, _, expert_idx = plain(p, x, cfg)
        return h, logits, probs, jnp.take_along_axis(probs, expert_idx, axis=-1), expert_idx

    return [(zoo, "route", route)]


def _residual_stream_in_bfloat16(zoo, jnp, jax):
    """The residual stream written in bfloat16 after each sub-block, as an
    implementation that keeps its activations in bfloat16 holds it."""
    def block(p, x, table, kind, cfg):
        window = cfg.sliding_window if kind == "sliding" else None
        x = _rounded(x + zoo.attention(p, x, table, window, cfg), jax)
        y, stats = zoo.moe(p, x, cfg)
        return _rounded(x + y, jax), stats

    return [(zoo, "block", block)]


def _bf16_router(zoo, jnp, jax):
    from elasticdl_tpu.ops import moe as moe_ops

    def route(p, x, cfg):
        h = zoo.rmsnorm(x, p["moe_norm"], cfg.rms_norm_eps).reshape(-1, x.shape[-1])
        logits = jnp.dot(h.astype(jnp.bfloat16),
                         p["moe_router"].astype(jnp.bfloat16)).astype(jnp.float32)
        probs, weights, idx = moe_ops.topk_route(logits, cfg.num_experts_per_tok)
        return h, logits, probs, weights / jnp.sum(weights, axis=-1, keepdims=True), idx

    return [(zoo, "route", route)]


# the nearest precision below the stated one, where the statement is float32:
# the router's logits and the residual stream
CONTROLS = {
    "a_bfloat16_router": _bf16_router,
    "residual_stream_in_bfloat16": _residual_stream_in_bfloat16,
}

DEPARTURES = {
    "window_one_key_short": _window(-1),
    "window_one_key_long": _window(+1),
    "yarn_table_on_the_sliding_layers": _tables(
        lambda tables, plain, cfg, t: {**tables, "sliding": tables["full"]}),
    "plain_table_on_the_full_layer": _tables(
        lambda tables, plain, cfg, t: {**tables, "full": tables["sliding"]}),
    "attention_factor_left_out": _tables(
        lambda tables, plain, cfg, t: plain(
            dataclasses.replace(cfg, attention_factor=1.0), t)),
    "topk_weights_not_renormalised": _weights_not_renormalised,
}


@contextlib.contextmanager
def applied(name, zoo):
    """The program with departure `name` patched in (None: as it is)."""
    import jax
    import jax.numpy as jnp

    patches = {**DEPARTURES, **CONTROLS}[name](zoo, jnp, jax) if name else []
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
    ap.add_argument("--workload", default="mellum2-12b-a2.5b.resident-16k")
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
        or [None] + sorted(CONTROLS) + sorted(DEPARTURES)
    wrong = 0
    for name in names:
        # a new trainer every time: the patched functions must be traced anew
        spec, mesh, trainer, zoo = fresh_trainer(driver, config, args.seed)
        with applied(name, zoo):
            verdict = driver.program_check(
                trainer, spec, mesh, zoo, reference, model_params, batches,
                lambda: trainer.init_state(batches[0]), lambda text: None)
        wrong += verdict["ok"] != (name is None)
        print(f"seed {args.seed} {name or 'the program as it is'}: correct: "
              f"{'true' if verdict['ok'] else 'false'}"
              f"{'' if verdict['ok'] == (name is None) else '  <-- UNEXPECTED'}; "
              f"failures: {verdict['failures']}; figures: {verdict['figures']}", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    raise SystemExit(main())
