"""`ops/ssm.py::causal_conv1d` on its two routes: the kernels of
`ops/pallas_conv1d.py` (interpret mode here) against the plain body — values,
and dx, dw, db against `jax.grad` of the plain body — over bias / no bias,
one sequence and two, time blocks of one strip and of two, two blocks and four
(the tile before a block and the tile after it both cross a block's edge), one
lane tile and three; causality on the kernel route; and the shapes and
backends that must stay on the plain body, bit for bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import pallas_attention, pallas_conv1d, ssm
from tests.conftest import pallas_calls

K = 4
# (tokens, rows of a time block): two blocks and four of one strip, two of two strips
LAYOUTS = {"two_blocks": (64, 32), "four_blocks": (128, 32), "two_strips_a_block": (128, 64)}
CASES = [(bias, batch, layout, channels)
         for bias in (True, False) for batch in (1, 2)
         for layout in LAYOUTS for channels in (128, 384)]
_ids = lambda c: f"{'bias' if c[0] else 'no_bias'}-b{c[1]}-{c[2]}-ch{c[3]}"


def operands(bias, batch, t, channels, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (batch, t, channels)),
            jax.random.normal(keys[1], (K, channels)),
            jax.random.normal(keys[2], (channels,)) if bias else None,
            jax.random.normal(keys[3], (batch, t, channels)))


@pytest.fixture
def interpret_kernels(monkeypatch):
    """The signal alone: the kernels run by `pallas_call(interpret=True)`,
    which a `jax.checkpoint` takes."""
    monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")


_RESULTS = {}


def both_routes(case):
    """(kernel route, plain body) -> (u, dx, dw[, db]) of `sum(u · weight)`,
    computed once a case."""
    if case not in _RESULTS:
        bias, batch, layout, channels = case
        t, block = LAYOUTS[layout]
        x, w, b, weight = operands(bias, batch, t, channels)
        plan = pallas_conv1d.Blocks(block, 128)
        args = (x, w) + ((b,) if bias else ())

        def results(conv):
            scalar = lambda *a: jnp.sum(conv(*a) * weight)
            return (conv(*args),) + jax.grad(scalar, argnums=tuple(range(len(args))))(*args)

        kernel = lambda x, w, b=None: pallas_conv1d.causal_conv1d_kernels(x, w, b, plan)
        _RESULTS[case] = (results(jax.jit(kernel)), results(jax.jit(ssm._causal_conv1d_plain)))
    return _RESULTS[case]


@pytest.mark.parametrize("case", CASES, ids=_ids)
def test_values_match_the_plain_body(case, interpret_kernels):
    (got, *_), (want, *_) = both_routes(case)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


GRADIENTS = [(case, operand) for case in CASES
             for operand in ("dx", "dw") + (("db",) if case[0] else ())]


@pytest.mark.parametrize("case,operand", GRADIENTS, ids=lambda v: v if isinstance(v, str) else _ids(v))
def test_gradient_matches_the_plain_body_s(case, operand, interpret_kernels):
    at = ("u", "dx", "dw", "db").index(operand)
    got, want = (results[at] for results in both_routes(case))
    assert got.dtype == jnp.float32 and got.shape == want.shape
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0
    np.testing.assert_allclose(got, want, atol=1e-4 * scale)


def test_a_later_token_changes_nothing_before_it_on_the_kernel_route(interpret_kernels):
    """Token 37 sits in the second time block's first strip: the first block
    and the rows before it are as they were, to the bit."""
    x, w, b, _ = operands(True, 2, 128, 256, seed=3)
    assert ssm.conv_route(x.shape, K) == "kernel"
    before = np.asarray(ssm.causal_conv1d(x, w, b))
    after = np.asarray(ssm.causal_conv1d(x.at[:, 37].add(1.0), w, b))
    np.testing.assert_array_equal(after[:, :37], before[:, :37])
    assert np.all(after[:, 37:37 + K] != before[:, 37:37 + K])
    np.testing.assert_array_equal(after[:, 37 + K:], before[:, 37 + K:])


def test_the_first_rows_see_zeros_before_the_sequence(interpret_kernels):
    """u_0 = bias + w_{K-1} x_0: nothing of another sequence or block leaks in."""
    x, w, b, _ = operands(True, 2, 64, 128, seed=5)
    got = ssm.causal_conv1d(x, w, b)
    np.testing.assert_allclose(got[:, 0], b + w[K - 1] * x[:, 0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got[:, 1], b + w[K - 2] * x[:, 0] + w[K - 1] * x[:, 1],
                               rtol=1e-6, atol=1e-6)


ROUTES = {
    # (shape, taps, interpret mode) -> route
    "whole_lanes_whole_blocks": ((2, 128, 256), K, True, "kernel"),
    "the_cell_s_kimi_plane": ((1, 16384, 4096), K, True, "kernel"),
    "the_cell_s_nemotron_plane": ((1, 8192, 6144), K, True, "kernel"),
    "ragged_tokens": ((2, 70, 256), K, True, "plain"),
    "tokens_short_of_a_strip": ((2, 24, 256), K, True, "plain"),
    "channels_not_whole_lanes": ((2, 128, 100), K, True, "plain"),
    "taps_past_a_tile": ((2, 128, 256), 10, True, "plain"),
    "the_bare_cpu": ((2, 128, 256), K, False, "plain"),
    "the_rehearsal_s_tiny_plane": ((2, 40, 64), K, False, "plain"),
}


@pytest.mark.parametrize("name", sorted(ROUTES))
def test_the_route_follows_the_shape_and_what_can_run(name, monkeypatch, route_log):
    shape, taps, interpret, want = ROUTES[name]
    if interpret:
        monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
    ssm._log_conv_route.cache_clear()
    assert ssm.conv_route(shape, taps) == want
    ssm.conv_route(shape, taps)             # logged once for each answer
    said = {r.getMessage() for r in route_log.records if "causal convolution" in r.getMessage()}
    said = sorted(said)     # a record reaches caplog twice where the package's logger propagates
    assert len(said) == 1
    assert f"takes the {want} route" in said[0]
    assert f"({shape[1]} tokens, {shape[2]} channels, {taps} taps)" in said[0]
    assert f"interpret mode: {interpret}" in said[0]


@pytest.mark.parametrize("name", ["ragged_tokens", "channels_not_whole_lanes", "the_bare_cpu"])
def test_the_plain_route_is_today_s_body_to_the_bit(name, monkeypatch):
    shape, _, interpret, _ = ROUTES[name]
    if interpret:
        monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
    x, w, b, _ = operands(True, *shape, seed=7)
    traced = jax.make_jaxpr(lambda *a: ssm.causal_conv1d(*a))(x, w, b).jaxpr
    assert pallas_calls(traced, "causal_conv1d_fwd") == 0
    np.testing.assert_array_equal(ssm.causal_conv1d(x, w, b),
                                  ssm._causal_conv1d_plain(x, w, b))
    np.testing.assert_array_equal(ssm.causal_conv1d(x, w), ssm._causal_conv1d_plain(x, w))


def test_a_recomputed_layer_runs_the_forward_kernel_again_and_keeps_nothing(
        interpret_kernels):
    """Under a `jax.checkpoint` with a names policy (both zoo models') nothing
    of the convolution is kept: the gradient's program holds the forward kernel
    twice — the pass and its recomputation — and the pull-back once."""
    x, w, b, weight = operands(True, 1, 64, 128)
    policy = jax.checkpoint_policies.save_only_these_names("nothing_of_this_layer")

    def loss(x, w, b):
        layer = jax.checkpoint(lambda x, w, b: jax.nn.silu(ssm.causal_conv1d(2.0 * x, w, b)),
                               policy=policy)
        return jnp.sum(layer(x, w, b) * weight)

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, w, b).jaxpr
    assert pallas_calls(traced, "causal_conv1d_fwd") == 2
    assert pallas_calls(traced, "causal_conv1d_bwd") == 1
    plain = lambda x, w, b: jnp.sum(jax.nn.silu(ssm._causal_conv1d_plain(2.0 * x, w, b)) * weight)
    got = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
    want = jax.grad(plain, argnums=(0, 1, 2))(x, w, b)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=1e-4 * float(jnp.max(jnp.abs(r))))


def test_operands_in_another_dtype_are_widened_not_narrowed(interpret_kernels):
    """The configurations state the convolution float32: a bfloat16 operand is
    widened on both routes and the result is float32."""
    x, w, b, _ = operands(True, 1, 64, 128)
    got = ssm.causal_conv1d(x.astype(jnp.bfloat16), w, b)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(
        got, ssm._causal_conv1d_plain(x.astype(jnp.bfloat16), w, b), rtol=1e-5, atol=1e-5)
