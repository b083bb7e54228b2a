"""`ops/ssm.py::gated_short_conv` — G ⊙ conv_K(B ⊙ u) on the three column
blocks of one projection — on both of `conv_route`'s routes (the kernels of
`ops/pallas_conv1d.py` under the interpret signal, the plain body without it)
against K shifted multiply-adds written out here: the values and the gradients
of the projection and of the taps, at K = 3 (LFM2's) and at the K = 4 the other
callers of `causal_conv1d` use; its three named scopes; and that the other
callers' operation is what it was.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import pallas_attention, ssm
from tests.conftest import pallas_calls

# (route, K, batch, tokens, channels): the kernels need channels of whole
# lanes and tokens of whole time blocks — 96 tokens are three blocks of 32, so
# the borrowed rows cross two block edges; 40 tokens never tile
CASES = [("plain", 3, 2, 40, 64), ("plain", 4, 2, 40, 64),
         ("kernel", 3, 2, 96, 128), ("kernel", 4, 1, 64, 256)]
_ids = lambda c: f"{c[0]}-k{c[1]}-b{c[2]}-t{c[3]}-ch{c[4]}"


def shifted_sums(bgu, weight):
    """The definition: three column blocks, K shifted multiply-adds."""
    k, c = weight.shape
    t = bgu.shape[1]
    b, g, u = bgu[..., :c], bgu[..., c:2 * c], bgu[..., 2 * c:]
    v = jnp.pad(b * u, ((0, 0), (k - 1, 0), (0, 0)))
    return g * sum(weight[j] * v[:, j:j + t] for j in range(k))


_RESULTS = {}


def both(case, monkeypatch):
    """((y, dbgu, dw) of the operation, the same of the definition), once a
    case."""
    if case not in _RESULTS:
        route, k, batch, t, channels = case
        if route == "kernel":
            monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
        assert ssm.conv_route((batch, t, channels), k) == route
        keys = jax.random.split(jax.random.PRNGKey(k), 3)
        bgu = jax.random.normal(keys[0], (batch, t, 3 * channels))
        weight = jax.random.uniform(keys[1], (k, channels), minval=-0.6, maxval=0.6)
        cotangent = jax.random.normal(keys[2], (batch, t, channels))

        def results(f):
            scalar = lambda bgu, w: jnp.sum(f(bgu, w) * cotangent)
            return (f(bgu, weight),) + jax.grad(scalar, argnums=(0, 1))(bgu, weight)

        # a new closure a route: a jitted function keeps its trace
        _RESULTS[case] = (results(jax.jit(lambda a, w: ssm.gated_short_conv(a, w))),
                          results(jax.jit(shifted_sums)))
    return _RESULTS[case]


@pytest.mark.parametrize("what", ["y", "dbgu", "dw"])
@pytest.mark.parametrize("case", CASES, ids=_ids)
def test_matches_the_shifted_multiply_adds(case, what, monkeypatch):
    got, want = (r[("y", "dbgu", "dw").index(what)] for r in both(case, monkeypatch))
    assert got.dtype == jnp.float32 and got.shape == want.shape
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0.1
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * scale)


@pytest.mark.parametrize("route", ["plain", "kernel"])
def test_the_convolution_between_the_gates_takes_conv_route_s_route(route, monkeypatch):
    """On the kernel route the traced operation holds `causal_conv1d_fwd` and,
    differentiated, `causal_conv1d_bwd`; on the plain one no kernel at all.
    The two products are XLA's on both."""
    if route == "kernel":
        monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
    bgu, weight = jnp.ones((1, 64, 3 * 128)), jnp.ones((3, 128))
    forward = jax.make_jaxpr(lambda a, w: ssm.gated_short_conv(a, w))(bgu, weight)
    backward = jax.make_jaxpr(jax.grad(lambda a, w: jnp.sum(ssm.gated_short_conv(a, w))))(
        bgu, weight)
    kernels = int(route == "kernel")
    assert pallas_calls(forward.jaxpr, "causal_conv1d_fwd") == kernels
    assert pallas_calls(forward.jaxpr, "causal_conv1d_bwd") == 0
    assert pallas_calls(backward.jaxpr, "causal_conv1d_bwd") == kernels


def test_each_part_is_under_a_scope_of_its_own():
    """`gate_in`, `conv`, `gate_out`: what a trace prices apart."""
    bgu, weight = jnp.ones((1, 40, 3 * 64)), jnp.ones((3, 64))
    text = jax.jit(lambda a, w: ssm.gated_short_conv(a, w)).lower(bgu, weight).as_text(
        debug_info=True)
    for scope in ("gate_in", "conv", "gate_out"):
        assert f"{scope}/" in text or f'{scope}"' in text, scope


def test_causal_a_later_token_changes_nothing_before_it():
    bgu = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 3 * 64))
    weight = jax.random.normal(jax.random.PRNGKey(1), (3, 64))
    later = bgu.at[:, 25:].add(1.0)
    np.testing.assert_array_equal(ssm.gated_short_conv(later, weight)[:, :25],
                                  ssm.gated_short_conv(bgu, weight)[:, :25])


@pytest.mark.parametrize("k", [3, 4])
def test_the_other_callers_convolution_is_untouched(k):
    """`causal_conv1d` alone, with its bias, is still its plain body's K
    shifted sums: nothing of the gates reaches Nemotron's, Kimi's or Phi's
    call."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 64))
    w = jax.random.normal(jax.random.PRNGKey(3), (k, 64))
    b = jax.random.normal(jax.random.PRNGKey(4), (64,))
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    want = b + sum(w[j] * padded[:, j:j + 40] for j in range(k))
    np.testing.assert_allclose(ssm.causal_conv1d(x, w, b), want, rtol=1e-5, atol=1e-5)
    # and the gated form with gates of one is that convolution without a bias
    ones = jnp.ones_like(x)
    np.testing.assert_allclose(
        ssm.gated_short_conv(jnp.concatenate([ones, ones, x], axis=-1), w),
        ssm.causal_conv1d(x, w), rtol=1e-6, atol=1e-6)
