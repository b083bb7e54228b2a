"""`ops/ssm.py::selective_scan` on its two routes: the kernels of
`ops/pallas_selective_scan.py` (interpret mode here) against the plain body,
and the plain body against the recurrence written token by token — values and
all six gradients (dx, dΔ, dA, dB, dC, dD) — over one sequence and two, one
time block and two (the state and its gradient cross a block's edge), one
channel block and two; a ragged tail on the plain body; what the route
answers; and that neither route keeps anything of (T, E, N) size for its
backward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import pallas_attention, pallas_selective_scan, ssm

N = 16
OPERANDS = ("x", "dt", "a", "b", "c", "d")


def operands(batch, t, channels, n=N, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    return ((jax.random.normal(keys[0], (batch, t, channels)),
             jax.nn.softplus(jax.random.normal(keys[1], (batch, t, channels)) - 1.0),
             -jnp.exp(0.5 * jax.random.normal(keys[2], (channels, n))),
             jax.random.normal(keys[3], (batch, t, n)),
             jax.random.normal(keys[4], (batch, t, n)),
             jax.random.normal(keys[5], (channels,))),
            jax.random.normal(keys[6], (batch, t, channels)))


def token_by_token(x, dt, a, b, c, d):
    """The recurrence as it is written: one token at a time, every state an
    array."""
    state, ys = jnp.zeros((x.shape[0],) + a.shape), []
    for t in range(x.shape[1]):
        state = (jnp.exp(dt[:, t, :, None] * a) * state
                 + (dt[:, t] * x[:, t])[..., None] * b[:, t, None, :])
        ys.append(jnp.sum(state * c[:, t, None, :], axis=-1) + d * x[:, t])
    return jnp.stack(ys, axis=1)


def value_and_gradients(scan, args, weight):
    return (scan(*args),) + jax.grad(
        lambda *a: jnp.sum(scan(*a) * weight), argnums=tuple(range(6)))(*args)


@pytest.fixture
def interpret_kernels(monkeypatch):
    """The signal alone: the kernels run by `pallas_call(interpret=True)`,
    which a `jax.checkpoint` takes."""
    monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")


# (sequences, tokens, channels, channels of a block)
KERNEL_CASES = {"one_block": (1, 128, 128, 128),
                "two_sequences_two_time_blocks": (2, 256, 128, 128),
                "two_channel_blocks": (1, 256, 256, 128)}
_KERNEL = {}


def kernel_and_plain(case):
    if case not in _KERNEL:
        batch, t, channels, lanes = KERNEL_CASES[case]
        args, weight = operands(batch, t, channels)
        plan = pallas_selective_scan.Blocks(pallas_selective_scan.TIME_BLOCK, lanes)
        kernel = jax.jit(lambda *a: pallas_selective_scan.selective_scan_kernels(*a, plan))
        _KERNEL[case] = (value_and_gradients(kernel, args, weight),
                         value_and_gradients(jax.jit(ssm._selective_scan_plain), args, weight))
    return _KERNEL[case]


@pytest.mark.parametrize("what", ("y",) + OPERANDS)
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_kernels_match_the_plain_body(case, what, interpret_kernels):
    at = (("y",) + OPERANDS).index(what)
    got, want = (results[at] for results in kernel_and_plain(case))
    assert got.dtype == jnp.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * float(jnp.max(jnp.abs(want))))


# (sequences, tokens, channels, state indices, tokens of a checkpointed block):
# whole blocks, and a tail of 4 tokens padded with Δ = 0
PLAIN_CASES = {"whole_blocks": (2, 16, 8, 4, 8), "ragged_tail": (2, 20, 8, 4, 8)}
_PLAIN = {}


def plain_and_loop(case):
    if case not in _PLAIN:
        batch, t, channels, n, block = PLAIN_CASES[case]
        args, weight = operands(batch, t, channels, n, seed=1)
        plain = lambda *a: ssm._selective_scan_plain(*a, block=block)
        _PLAIN[case] = (value_and_gradients(plain, args, weight),
                        value_and_gradients(token_by_token, args, weight))
    return _PLAIN[case]


@pytest.mark.parametrize("what", ("y",) + OPERANDS)
@pytest.mark.parametrize("case", sorted(PLAIN_CASES))
def test_the_plain_body_matches_the_recurrence_token_by_token(case, what):
    at = (("y",) + OPERANDS).index(what)
    got, want = (results[at] for results in plain_and_loop(case))
    assert got.dtype == jnp.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6 * float(jnp.max(jnp.abs(want))))


def test_selective_scan_takes_the_kernels_where_the_route_says_so(interpret_kernels):
    """Through the public function, under a `jax.checkpoint` as a layer calls
    it: the kernel route's values are the plain body's."""
    args, _ = operands(1, 128, 128, seed=2)
    assert ssm.selective_scan_route(args[0].shape, N) == "kernel"
    got = jax.jit(jax.checkpoint(ssm.selective_scan))(*args)
    np.testing.assert_allclose(got, ssm._selective_scan_plain(*args), rtol=2e-4, atol=1e-4)


# (x's shape, state indices, the kernels runnable) -> the route
ROUTES = [
    ((1, 8192, 5120), 16, True, "kernel"),       # the benchmark cell's plane
    ((2, 256, 128), 8, True, "kernel"),
    ((1, 8192, 5120), 16, False, "plain"),       # no TPU, no interpret signal
    ((1, 40, 128), 16, True, "plain"),           # tokens no whole time block
    ((1, 8200, 5120), 16, True, "plain"),
    ((1, 256, 96), 16, True, "plain"),           # channels no whole lanes
    ((1, 256, 128), 4, True, "plain"),           # state indices no whole sublane tile
]


@pytest.mark.parametrize("shape,n,runnable,route", ROUTES,
                         ids=[f"{s[1]}x{s[2]}x{n}-{'runnable' if r else 'cpu'}"
                              for s, n, r, _ in ROUTES])
def test_the_route_is_a_pure_function_of_the_shapes(shape, n, runnable, route, monkeypatch,
                                                    route_log):
    if runnable:
        monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
    else:
        monkeypatch.delenv(pallas_attention._INTERPRET_ENV, raising=False)
    ssm._log_selective_scan_route.cache_clear()
    assert ssm.selective_scan_route(shape, n) == route
    assert ssm.selective_scan_route(shape, n) == route
    # (a set: a record reaches caplog twice where the package's logger propagates)
    said = sorted({r.getMessage() for r in route_log.records if "selective scan" in r.getMessage()})
    assert len(said) == 1 and f"takes the {route} route" in said[0]      # once a shape


@pytest.mark.parametrize("route", ("kernel", "plain"))
def test_nothing_of_the_state_s_size_over_time_is_kept_for_the_backward(route, monkeypatch):
    """(T, E, N) float32 is 2.68 GB a layer at the cell's shape: the kernels
    keep their operands and a state a time block, the plain body a state a
    checkpointed block."""
    if route == "kernel":
        monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
    batch, t, channels = 1, 256, 128
    args, _ = operands(batch, t, channels, seed=3)
    assert ssm.selective_scan_route(args[0].shape, N) == route
    _, pullback = jax.vjp(ssm.selective_scan, *args)
    kept = [leaf for leaf in jax.tree_util.tree_leaves(pullback) if hasattr(leaf, "size")]
    # the largest is an operand's plane: a sixteenth of the states over time
    assert kept and max(leaf.size for leaf in kept) == batch * t * channels
