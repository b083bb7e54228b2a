"""The departures the Ouro cell's check must catch and the precision controls
its limits are read against, and a command that runs the cell's check — the
driver's own `program_check` — under each of them on the chip at full width:

    chiprun --chips 1 --timeout 3300 -- python3 benchmark/rehearse/departures_ouro.py \
        [--seed N] [--only name,name] [--seeds a,b,c] [--check_steps 2] \
        [--reference_operands float32] [--model_params 'k=v;k=v' --seq_len T]

(the last two: a smaller program, for a CPU; `--reference_operands float32`:
the reference's matmuls with unrounded operands, what the limits were first
read against.) `DEPARTURES` and `CONTROLS` are patches of the PROGRAM (the zoo module);
`CONTROLS` keep ONE thing the configuration states float32 in bfloat16.
`REFERENCE_CONTROLS` put the plain reference, computed in the nearest
precision below the stated one, in the program's place: its two steps are
compared with the reference's own as the program's are.

Every line it prints holds `correct: true|false`, the failures and every
figure of the comparison. The unpatched program must read true, every
departure and every control false — or be written down in `BELOW_THE_NOISE`
with its figure. The CPU tests (`tests/test_ouro_check.py`) apply the same
patches at the tiny preset. None of this is run by the benchmark; nothing here
is an option of the program.
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
fresh_trainer, _rounded = _glm.fresh_trainer, _glm._rounded


def _passes(each_pass):
    """The loop as `each_pass(zoo, jax, params, x, cfg, t) -> (the state exit
    t reads, what pass t + 1 starts from)` runs a pass."""
    def patch(zoo, jnp, jax):
        def passes(params, x, cfg):
            states = []
            for t in range(cfg.total_ut_steps):
                state, x = each_pass(zoo, jax, params, x, cfg, t)
                states.append(state)
            return jnp.stack(states)

        return [(zoo, "passes", passes)]
    return patch


def _second_pass_out_of_the_gradient(zoo, jax, params, x, cfg, t):
    """The second pass's use of the layers' weights adds nothing to their
    gradient (the stream's own gradient still flows through it)."""
    if t == 1:
        params = {k: jax.lax.stop_gradient(v) if k in zoo.LAYER_KEYS else v
                  for k, v in params.items()}
    state = zoo.run_pass(params, x, cfg)
    return state, state


def _no_norm_between_passes(zoo, jax, params, x, cfg, t):
    """The exits read the normed state; the next pass starts from the stack's
    own output."""
    raw = zoo.stack(params, x, cfg)
    return zoo.rmsnorm(raw, params["final_norm"], cfg.rms_norm_eps), raw


def _entropy_times(factor):
    def patch(zoo, jnp, jax):
        plain = zoo.entropy
        return [(zoo, "entropy", lambda p: factor * plain(p))]
    return patch


def _gate_bias_left_out(zoo, jnp, jax):
    plain = zoo.exit_gates
    return [(zoo, "exit_gates", lambda params, states: plain(
        {**params, "exit_gate_b": jnp.zeros_like(params["exit_gate_b"])}, states))]


def _residual_stream_in_bfloat16(zoo, jnp, jax):
    """The residual stream written in bfloat16 after each layer application,
    as an implementation that keeps its activations in bfloat16 holds it."""
    plain = zoo.layer
    return [(zoo, "layer", lambda p, x, cfg: _rounded(plain(p, x, cfg), jax))]


def _bfloat16_logits(zoo, jnp, jax):
    """Every exit's cross entropy from logits written in bfloat16."""
    plain = zoo.head_logits
    return [(zoo, "head_logits", lambda state, head: _rounded(plain(state, head), jax))]


# the nearest precision below the stated one, where the statement is float32:
# the residual stream and the logits the cross entropy reads
CONTROLS = {
    "residual_stream_in_bfloat16": _residual_stream_in_bfloat16,
    "cross_entropy_from_bfloat16_logits": _bfloat16_logits,
}

# name -> (what of the reference's `hyper` changes, what its parameters and
# moments are kept in): everything the configuration states float32 computed
# in bfloat16 from float32 master weights and moments, and with those in
# bfloat16 too
REFERENCE_CONTROLS = {
    "reference_in_bfloat16_float32_optimizer": ({"dtype": "bfloat16"}, "float32"),
    "reference_in_bfloat16": ({"dtype": "bfloat16"}, "bfloat16"),
}

DEPARTURES = {
    "second_pass_left_out_of_the_shared_gradient": _passes(_second_pass_out_of_the_gradient),
    "norm_between_passes_left_out": _passes(_no_norm_between_passes),
    "entropy_sign_flipped": _entropy_times(-1.0),
    "entropy_coef_doubled": _entropy_times(2.0),
    "gate_bias_left_out": _gate_bias_left_out,
}

# What the check on the chip could NOT tell from the program as it is, with
# the figures that moved most, (control, the program as it is) at the same
# seed (2147483777; my chip runs, PR 52, review session), against the
# reference with the operands the configuration states. Over nine seeds the
# program as it is reads `mu_rel_l2.head` 0.0009-0.0028, `mu_rel_l2.w_gate`
# 0.0026-0.0072 and `mu_rel_l2.exit_gate_w` 0.0012-0.0090: a seed moves them
# more than either control does (0.9-1.4 times at both seeds tried). Against
# unrounded operands neither moved anything (`mu_rel_l2.wq` 0.0166 for 0.0160).
BELOW_THE_NOISE = {
    "residual_stream_in_bfloat16": {"mu_rel_l2.head": (0.00127, 0.00094),
                                    "mu_rel_l2.w_gate": (0.00322, 0.00270),
                                    "update_rel_l2.head": (0.0177, 0.0151)},
    "cross_entropy_from_bfloat16_logits": {"mu_rel_l2.exit_gate_w": (0.00246, 0.00193),
                                           "mu_rel_l2.head": (0.00094, 0.00094),
                                           "loss_rel": (5.2e-6, 6.7e-6)},
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


def reference_in_the_program_s_place(name, driver, reference, model_params, batches,
                                     params0, reference_steps) -> dict:
    """`compare()`'s verdict with the reference's own steps under
    `REFERENCE_CONTROLS[name]` (by `reference_steps`, the driver's) standing
    where the program's are read."""
    import numpy as np

    changes, kept_in = REFERENCE_CONTROLS[name]
    low = driver.DenseStepCheck(reference, model_params, batches)
    low.hp = {**low.hp, **changes}
    low.params0 = {k: v.astype(kept_in) for k, v in params0.items()}
    got = reference_steps(low)
    checker = driver.DenseStepCheck(reference, model_params, batches)
    checker.params0 = params0
    checker.got = {k: ({leaf: np.asarray(v, np.float32) for leaf, v in got[k].items()}
                       if k in ("mu", "params") else got[k]) for k in got}
    return checker.compare()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="ouro-2.6b.resident-4k")
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--seeds", default="",
                    help="run `--only` (default: the program AS IT IS) at each of these "
                         "seeds and print every figure: what the tolerances are derived from")
    ap.add_argument("--only", default="")
    ap.add_argument("--check_steps", type=int, default=0)
    ap.add_argument("--model_params", default="",
                    help="k=v;k=v over the configuration's: a smaller program, on a CPU")
    ap.add_argument("--seq_len", type=int, default=0)
    ap.add_argument("--reference_operands", default="",
                    help="float32: the reference's matmuls with unrounded operands, "
                         "whatever the configuration states")
    args = ap.parse_args(argv)

    if args.seeds:
        return sum(main(["--workload", args.workload, "--seed", seed,
                         "--only", args.only or "none",
                         "--check_steps", str(args.check_steps),
                         "--model_params", args.model_params,
                         "--seq_len", str(args.seq_len),
                         "--reference_operands", args.reference_operands])
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
    as_stated = reference.hyper
    if args.reference_operands:
        reference.hyper = lambda model_params: {
            **as_stated(model_params), "matmul_operands": args.reference_operands}
    steps = args.check_steps or int(traffic["check_steps"])
    batch = int(traffic["batch_per_chip"])
    tokens = driver._lm.tokens_from_seed(
        args.seed, steps * batch, args.seq_len or int(traffic["seq_len"]),
        int(model_params["vocab_size"]), float(traffic["zipf_s"]))
    batches = driver._lm._batches(tokens, batch, 0, steps)
    names = [None if n == "none" else n for n in args.only.split(",") if n] \
        or [None] + sorted(REFERENCE_CONTROLS) + sorted(CONTROLS) + sorted(DEPARTURES)
    # the reference's steps start from the seed's parameters and batches, which
    # no patch of the program touches: computed once a seed
    plain, wanted = driver.DenseStepCheck.reference_steps, []

    def once(self):
        if not wanted:
            wanted.append(plain(self))
        return wanted[0]

    driver.DenseStepCheck.reference_steps = once
    wrong = 0
    try:
        for name in names:
            # a new trainer every time: the patched functions must be traced anew
            spec, mesh, trainer, zoo = fresh_trainer(driver, config, args.seed)
            if name in REFERENCE_CONTROLS:
                verdict = reference_in_the_program_s_place(
                    name, driver, reference, model_params, batches,
                    driver.check_lm._host(trainer.init_state(batches[0]).params), plain)
            else:
                with applied(name, zoo):
                    verdict = driver.program_check(
                        trainer, spec, mesh, zoo, reference, model_params, batches,
                        lambda: trainer.init_state(batches[0]), lambda text: None)
            expected = name is None or name in BELOW_THE_NOISE
            wrong += verdict["ok"] != expected
            print(f"seed {args.seed} {name or 'the program as it is'}: correct: "
                  f"{'true' if verdict['ok'] else 'false'}"
                  f"{'' if verdict['ok'] == expected else '  <-- UNEXPECTED'}; "
                  f"failures: {verdict['failures']}; figures: {verdict['figures']}",
                  flush=True)
    finally:        # the next seed's reference is its own
        driver.DenseStepCheck.reference_steps = plain
        reference.hyper = as_stated
    return 1 if wrong else 0


if __name__ == "__main__":
    raise SystemExit(main())
