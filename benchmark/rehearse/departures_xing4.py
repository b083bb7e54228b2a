"""The departures the Xing4.0 cell's check must catch and the precision
controls its limits are read against (`CONTROLS`: what the configuration states
float32, kept in bfloat16), each as a patch of the PROGRAM (the zoo module and
the modules it calls), and a command that runs the cell's check — the driver's
own `program_check` — under each of them on the chip at full width:

    chiprun --chips 1 --timeout 3000 -- python3 benchmark/rehearse/departures_xing4.py \
        [--seeds a,b,c] [--only none,name,name] [--check_steps 2]

(every case named, `none` the program as it is, at every seed, in one process;
at most eight cases a process: twelve met the machine's 40 GiB of host memory
in PR 48.) Every line it prints holds `correct: true|false`, the failures and
every figure of the comparison. The unpatched program must read true, every
departure and every control false; `BELOW_THE_NOISE` names what this check
cannot see at seeded weights (it reads true, and says so): the coefficients
before the Sinkhorn rounds in bfloat16 (PERF.md §6-7, PR 48). The streams have
no control: the configuration states them bfloat16, the lowest precision the
program has, because the check could not tell float32 streams from them. The
CPU tests (`tests/test_xing4_check.py`) apply the same patches at the tiny
preset. None of this is run by the benchmark; nothing here is an option of the
program. The routers' patches are `departures_nemotron_h.py`'s and
`departures_glm4_moe_lite.py`'s (one router: GLM's `route`, which the zoo
module calls).
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
_renormalised, _rounded = _glm._renormalised, _glm._rounded


def _route_with(change):
    """`departures_nemotron_h.py`'s patch of the router's weights rule."""
    return lambda zoo, moe_ops, jnp, jax: _glm._route_with(change)(
        zoo, moe_ops, None, jnp, jax)


def _configured(**changes):
    """The forward pass under a configuration changed in `changes`."""
    def patch(zoo, moe_ops, jnp, jax):
        plain = zoo.forward
        return [(zoo, "forward", lambda params, bias, tokens, cfg: plain(
            params, bias, tokens, dataclasses.replace(cfg, **changes)))]
    return patch


def _h_res_the_identity(zoo, moe_ops, jnp, jax):
    def identity(m, iters, eps):
        eye = jnp.eye(m.shape[0], dtype=m.dtype)
        return jnp.broadcast_to(eye.reshape(eye.shape + (1,) * (m.ndim - 2)), m.shape)
    return [(zoo, "sinkhorn", identity)]


def _h_post_without_its_factor_2(zoo, moe_ops, jnp, jax):
    plain = zoo.mhc_write
    return [(zoo, "mhc_write", lambda streams, y, h_post, h_res: plain(
        streams, y, 0.5 * h_post, h_res))]


def _v_at_the_wrong_128(zoo, moe_ops, jnp, jax):
    """v read from the FIRST `v_head_dim` columns of a head's slice of
    W_kvb's output — the non-rotary key's — in place of the last."""
    glm = zoo.glm
    plain_attention, plain_full = glm.latent_attention, glm.full_attention

    def attention(*args, **kwargs):
        glm.full_attention = lambda q, k, v, causal=True: plain_full(
            q, k, k[..., :v.shape[-1]], causal=causal)
        try:
            return plain_attention(*args, **kwargs)
        finally:
            glm.full_attention = plain_full

    return [(glm, "latent_attention", attention)]


def _bf16_router(zoo, moe_ops, jnp, jax):
    return _glm._bf16_router(zoo.glm, moe_ops, None, jnp, jax)


def _sinkhorn_in_bfloat16(zoo, moe_ops, jnp, jax):
    """Every value of the twenty rounds kept at bfloat16's eight bits."""
    r = lambda x: _rounded(x, jax)

    def sinkhorn(m, iters, eps):
        m = r(m)
        for _ in range(iters):
            m = r(m / r(jnp.sum(m, axis=1, keepdims=True) + eps))
            m = r(m / r(jnp.sum(m, axis=0, keepdims=True) + eps))
        return m

    return [(zoo, "sinkhorn", sinkhorn)]


def _coefficients_in_bfloat16(zoo, moe_ops, jnp, jax):
    """The norm over a token's state, phi's matmul, the gates and biases, the
    sigmoids and the exponential at bfloat16's eight bits (the Sinkhorn rounds
    stay float32): `mhc_coefficients` written again with every value rounded."""
    r = lambda x: _rounded(x, jax)

    def coefficients(p, streams, cfg):
        n, b, t, c = streams.shape
        x = r(streams.astype(jnp.float32))
        inv_rms = r(jax.lax.rsqrt(r(jnp.mean(r(x * x), axis=(0, 3))) + cfg.hc_eps))
        raw = r(r(jnp.einsum("nbtc,nck->kbt", x.astype(jnp.bfloat16),
                             p["hc_phi"].reshape(n, c, -1).astype(jnp.bfloat16),
                             preferred_element_type=jnp.float32)) * inv_rms)
        alpha = jnp.concatenate([jnp.broadcast_to(p["hc_alpha"][i], (k,))
                                 for i, k in enumerate((n, n, n * n))])
        raw = r(r(alpha)[:, None, None] * raw + r(p["hc_b"])[:, None, None])
        h_pre = r(jax.nn.sigmoid(raw[:n]))
        h_post = r(2.0 * jax.nn.sigmoid(raw[n:2 * n]))
        positive = r(jnp.exp(jnp.clip(raw[2 * n:], cfg.mhc_h_res_clamp_min,
                                      cfg.mhc_h_res_clamp_max))).reshape(n, n, b, t)
        return h_pre, h_post, zoo.sinkhorn(positive, cfg.hc_sinkhorn_iters, cfg.hc_eps)

    return [(zoo, "mhc_coefficients", coefficients)]


# the nearest precision below the stated one, where the statement is float32
# and the chip's check can see it: the Sinkhorn rounds and the router's scores
CONTROLS = {
    "sinkhorn_in_bfloat16": _sinkhorn_in_bfloat16,
    "a_bfloat16_router": _bf16_router,
}
# what the check reads `correct: true` on the chip, kept for a check that can
# see it (PERF.md §7, PR 48)
BELOW_THE_NOISE = {
    "coefficients_in_bfloat16": _coefficients_in_bfloat16,
}
DEPARTURES = {
    "ten_sinkhorn_rounds": _configured(hc_sinkhorn_iters=10),
    "h_res_the_identity": _h_res_the_identity,
    "h_post_without_its_factor_2": _h_post_without_its_factor_2,
    "yarn_softmax_factor_left_out": _configured(mscale=0.0, mscale_all_dim=0.0),
    "v_at_the_wrong_128": _v_at_the_wrong_128,
    "renormalisation_left_out": _route_with(
        lambda jnp, chosen, bias, scale: scale * chosen),
    "scaling_factor_left_out": _route_with(
        lambda jnp, chosen, bias, scale: _renormalised(jnp, chosen, 1.0)),
}


@contextlib.contextmanager
def applied(name, zoo):
    """The program with departure `name` patched in (None: as it is)."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import moe as moe_ops

    patches = ({**DEPARTURES, **CONTROLS, **BELOW_THE_NOISE}[name](zoo, moe_ops, jnp, jax)
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
    ap.add_argument("--workload", default="xing4.0-29b-a4b.resident-4k")
    ap.add_argument("--seeds", default="2147483777",
                    help="every case of --only at each of these seeds")
    ap.add_argument("--only", default="",
                    help="`none` is the program as it is; default: every case")
    ap.add_argument("--check_steps", type=int, default=0)
    args = ap.parse_args(argv)

    resolved = common.resolve_cell(args.workload)
    config, traffic = resolved["config"], resolved["traffic"]
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
        fresh_state = None
        for name in names:
            # a new trainer every time: the patched functions must be traced anew
            spec, mesh, trainer, zoo = _glm.fresh_trainer(driver, config, seed)
            if fresh_state is None:
                # the selection bias as the cell settles it, by the program AS
                # IT IS, once a seed: every departure starts from the same state
                fresh_state = driver.settled_state_maker(
                    trainer, zoo, spec, reference, batches,
                    int(traffic["settle_router_steps"]),
                    (hp["first_expert"], hp["n_routed_experts"]), lambda text: None)
            with applied(name, zoo):
                verdict = driver.program_check(
                    trainer, spec, mesh, zoo, reference, model_params, batches,
                    fresh_state, lambda text: None)
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
