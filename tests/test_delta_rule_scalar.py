"""`ops/delta_rule.py`'s SCALAR form — one log-decay a head, g (B, T, H_v), key
heads shared by r value heads — against the per-token recurrence
(`delta_rule_recurrent`, which takes the scalar g and the grouped heads too),
float32 on the CPU: values and the gradients of q, k, v, g, β and the initial
state, at r = 1 and r = 2, over decays from mild to so strong that a quotient
form would overflow, at a chunk count (5) that is no multiple of
`chunks_per_block` (3); on the plain route and, at lane-wide heads, on the
KERNEL route (`ops/pallas_delta_rule.py`'s `delta_rule_scalar_*` in interpret
mode). The tie between the two forms: the scalar g laid against every channel
and sent through the CHANNEL form gives the same values.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import delta_rule as dr
from elasticdl_tpu.ops import pallas_attention, pallas_delta_rule
from tests.conftest import equations, listening, pallas_calls

B, T = 2, 70
CHUNK, PER_BLOCK = 16, 3
OPERANDS = ("q", "k", "v", "g", "beta", "initial_state")
STRENGTHS = [1.0, 8.0]
# (key heads, value heads, channels): r = 1 and r = 2
NARROW = [(3, 3, 8), (2, 4, 8)]
WIDE = [(2, 2, 128), (1, 2, 128)]


def operands(hk, hv, d, strength, seed=0):
    """Unit keys and queries, write strengths in (0, 1), ONE log-decay a value
    head −strength · softplus(normal), a state to start from."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    return (unit(jax.random.normal(keys[0], (B, T, hk, d))),
            unit(jax.random.normal(keys[1], (B, T, hk, d))),
            jax.random.normal(keys[2], (B, T, hv, d)),
            -strength * jax.nn.softplus(jax.random.normal(keys[3], (B, T, hv))),
            jax.nn.sigmoid(jax.random.normal(keys[4], (B, T, hv))),
            0.1 * jax.random.normal(keys[5], (B, hv, d, d)))


def chunked(q, k, v, g, beta, state):
    return dr.gated_delta_rule(q, k, v, g, beta, chunk=CHUNK, chunks_per_block=PER_BLOCK,
                               compute_dtype=jnp.float32, initial_state=state)


def weighted(rule, weight):
    """A scalar of both results, so that every gradient is exercised."""
    def scalar(*args):
        o, last = rule(*args)
        return jnp.sum(o * weight) + jnp.sum(last * last)
    return scalar


_MADE = {}


def results(rule_name, heads, strength):
    """(values, gradients) of one rule at one shape and decay, made once for
    the cases that read them. The kernel route's are made under the interpret
    signal by the caller's fixture."""
    key = (rule_name, heads, strength)
    if key not in _MADE:
        args = operands(*heads, strength)
        weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
        rule = dr.delta_rule_recurrent if rule_name == "recurrent" else chunked
        with jax.default_matmul_precision("highest"):
            # a new lambda a route: `jax.jit` keeps a function's trace
            values = jax.jit(lambda *a: rule(*a))(*args)
            grads = jax.jit(jax.grad(weighted(lambda *a: rule(*a), weight),
                                     argnums=range(6)))(*args)
        _MADE[key] = (values, grads)
    return _MADE[key]


def assert_matches(got, want, operand):
    (values, grads), (want_values, want_grads) = got, want
    if operand is None:
        for a, b in zip(values, want_values):
            assert a.shape == b.shape and np.all(np.isfinite(a))
            np.testing.assert_allclose(a, b, atol=3e-5)
        return
    a, b = grads[operand], want_grads[operand]
    scale = float(jnp.max(jnp.abs(b)))
    assert a.shape == b.shape and scale > 0 and np.all(np.isfinite(a))
    np.testing.assert_allclose(a, b, atol=5e-5 * scale)


CASES = [None] + list(range(6))
CASE_IDS = ["values"] + list(OPERANDS)


@pytest.mark.parametrize("operand", CASES, ids=CASE_IDS)
@pytest.mark.parametrize("strength", STRENGTHS)
@pytest.mark.parametrize("heads", NARROW, ids=["r1", "r2"])
def test_the_plain_route_matches_the_recurrence(heads, strength, operand):
    assert dr.delta_rule_route((B, T) + (heads[0], heads[2]), CHUNK, PER_BLOCK, heads[2],
                               "scalar", heads[1] // heads[0]) == "plain"
    assert_matches(results("plain", heads, strength),
                   results("recurrent", heads, strength), operand)


@pytest.fixture
def interpret_kernels(monkeypatch):
    monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")


@pytest.mark.parametrize("operand", CASES, ids=CASE_IDS)
@pytest.mark.parametrize("strength", STRENGTHS)
@pytest.mark.parametrize("heads", WIDE, ids=["r1", "r2"])
def test_the_kernels_match_the_recurrence(interpret_kernels, heads, strength, operand):
    assert dr.delta_rule_route((B, T) + (heads[0], heads[2]), CHUNK, PER_BLOCK, heads[2],
                               "scalar", heads[1] // heads[0]) == "kernel"
    assert_matches(results("kernel", heads, strength),
                   results("recurrent", heads, strength), operand)


def test_the_kernel_route_is_two_scalar_kernels_and_keeps_no_grouped_copy(interpret_kernels):
    """One forward and one backward `pallas_call`, under their own names; q
    and k enter them at their own H_k heads, and dq, dk leave them so."""
    hk, hv, d = WIDE[1]
    args = operands(hk, hv, d, 1.0)
    weight = jnp.ones_like(args[2])
    jaxpr = jax.make_jaxpr(jax.grad(weighted(lambda *a: chunked(*a), weight),
                                    argnums=range(6)))(*args)
    assert pallas_calls(jaxpr, "delta_rule_scalar_fwd") == 1
    assert pallas_calls(jaxpr, "delta_rule_scalar_bwd") == 1
    assert pallas_calls(jaxpr, "delta_rule_fwd") == pallas_calls(jaxpr, "delta_rule_bwd") == 0
    # q, k in and dq, dk out: (B, padded T, H_k · d), never H_v wide
    planes = []
    equations(jaxpr, lambda eqn: eqn.primitive.name == "pallas_call"
              and eqn.params["name"] == "delta_rule_scalar_bwd" and planes.extend(
                  v.aval.shape for v in list(eqn.invars) + list(eqn.outvars)
                  if len(v.aval.shape) == 3))
    assert planes.count((B, 96, hk * d)) == 4 and planes.count((B, 96, hv * d)) == 3


@pytest.mark.parametrize("heads", NARROW, ids=["r1", "r2"])
def test_a_scalar_decay_laid_against_every_channel_is_the_channel_form(heads):
    """The tie between the two forms: g (B, T, H_v) broadcast to (B, T, H_v,
    d) — and q, k repeated to the value heads — through the CHANNEL form's own
    body gives the scalar form's values."""
    hk, hv, d = heads
    q, k, v, g, beta, state = operands(hk, hv, d, 1.0)
    repeat = lambda a: jnp.repeat(a, hv // hk, axis=2)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: chunked(*a))(
            repeat(q), repeat(k), v, jnp.broadcast_to(g[..., None], g.shape + (d,)), beta, state)
    for a, b in zip(results("plain", heads, 1.0)[0], want):
        np.testing.assert_allclose(a, b, atol=3e-5)


def test_the_route_says_which_form_in_the_log(caplog):
    dr._log_route.cache_clear()
    with listening(caplog, dr.logger.name):
        dr.delta_rule_route((1, 256, 16, 128), 64, 4, 128, "scalar", 2)
        dr.delta_rule_route((1, 256, 32, 128), 64, 4)
    said = [r.getMessage() for r in caplog.records]
    assert any("SCALAR form, one decay a head and 2 value head(s) a key head" in s
               and "plain route" in s for s in said)
    assert any("a decay a channel" in s for s in said)


def test_the_forms_are_told_apart_by_the_shape_of_g():
    q, k, v, g, beta, _ = operands(2, 4, 8, 1.0)
    with pytest.raises(ValueError, match="channel form"):
        dr.gated_delta_rule(q, k, v, jnp.zeros(v.shape), beta)      # grouped heads need a scalar g
    with pytest.raises(ValueError, match="key heads"):
        dr.gated_delta_rule(q, k, v[:, :, :3], g[:, :, :3], beta[:, :, :3])       # 2 against 3


def test_the_scalar_kernels_plan_their_vmem():
    plan = pallas_delta_rule.scalar_blocks(128, 128, 64, 4, 2)
    assert plan is not None and plan.vmem_bytes <= pallas_delta_rule._vmem_bytes() // 2
    assert pallas_delta_rule.scalar_blocks(128, 64, 64, 4, 2) is None       # d_k != d_v
    assert pallas_delta_rule.scalar_blocks(64, 64, 64, 4, 1) is None        # not whole lanes
    assert pallas_delta_rule.scalar_blocks(128, 128, 12, 4, 1) is None      # not whole sublane tiles
