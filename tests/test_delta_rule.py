"""`ops/delta_rule.py`: the chunked gated delta rule with a decay for every
channel against the per-token recurrence, float32 on the CPU — values and all
five gradients over chunk lengths, block lengths and decays from mild to so
strong that a quotient form `(K ⊙ exp Γ)(K ⊘ exp Γ)ᵀ` would overflow; the
rule's two limits (β = 0: pure decay; g = 0 with orthonormal keys: a pure
delta rule); and, with one decay a head and the erase term off, `ops/ssm.py`'s
recurrence on the same operands. The same values and gradients on the KERNEL
route (`ops/pallas_delta_rule.py` in interpret mode) at lane-wide heads, and
the two routes against each other in both forms. Γ's own function
(`cumulative_log_decay`, a triangular product since PR 65) against numpy's
float64 running sum, its pull-back, and what it lowers to.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import delta_rule as dr
from elasticdl_tpu.ops import pallas_attention, ssm
from tests.conftest import pallas_calls

B, T, H, D = 2, 70, 3, 8
OPERANDS = ("q", "k", "v", "g", "beta")


def operands(strength=1.0, seed=0, t=T, dv=None, heads=H, d=D):
    """Unit keys and queries, write strengths in (0, 1), log-decays
    −strength · softplus(normal): at 8 a step decays by up to e^-30."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    return (unit(jax.random.normal(keys[0], (B, t, heads, d))),
            unit(jax.random.normal(keys[1], (B, t, heads, d))),
            jax.random.normal(keys[2], (B, t, heads, dv or d)),
            -strength * jax.nn.softplus(jax.random.normal(keys[3], (B, t, heads, d))),
            jax.nn.sigmoid(jax.random.normal(keys[4], (B, t, heads))))


def chunked(chunk, chunks_per_block):
    return lambda *args: dr.gated_delta_rule(
        *args, chunk=chunk, chunks_per_block=chunks_per_block, compute_dtype=jnp.float32)


def weighted(rule, weight):
    """A scalar of both results, so that every gradient is exercised."""
    def scalar(*args):
        o, last = rule(*args)
        return jnp.sum(o * weight) + jnp.sum(last)
    return scalar


# chunks that divide T = 70 and that do not (the tail is padded), shorter
# than a sub-block (8), one sub-block, two, four, eight; one block for the
# sequence and many
SHAPES = [(16, 1), (32, 2), (8, 3), (64, 8), (6, 7), (16, 100)]
# at 8 the in-chunk cumulative decay passes e^-88 = float32's smallest normal
# many times over: exp(−Γ) is inf
STRENGTHS = [0.1, 1.0, 8.0]


@pytest.fixture(scope="module", params=STRENGTHS)
def recurrent(request):
    args = operands(request.param)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    with jax.default_matmul_precision("highest"):
        values = jax.jit(dr.delta_rule_recurrent)(*args)
        grads = jax.jit(jax.grad(weighted(dr.delta_rule_recurrent, weight),
                                 argnums=range(5)))(*args)
    return args, weight, values, grads


@pytest.mark.parametrize("chunk, per_block", SHAPES)
def test_values_match_the_recurrence(recurrent, chunk, per_block):
    args, _, (want, want_last), _ = recurrent
    with jax.default_matmul_precision("highest"):
        got, last = jax.jit(chunked(chunk, per_block))(*args)
    assert got.shape == want.shape and last.shape == want_last.shape
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(last, want_last, atol=2e-5)


_CHUNKED_GRADIENTS = {}


def chunked_gradients(recurrent, chunk, per_block):
    """All five gradients of one (decay, shape), made once for the five cases
    that read them."""
    args, weight, _, _ = recurrent
    key = (float(args[3][0, 0, 0, 0]), chunk, per_block)
    if key not in _CHUNKED_GRADIENTS:
        with jax.default_matmul_precision("highest"):
            _CHUNKED_GRADIENTS[key] = jax.jit(jax.grad(
                weighted(chunked(chunk, per_block), weight), argnums=range(5)))(*args)
    return _CHUNKED_GRADIENTS[key]


@pytest.mark.parametrize("chunk, per_block", SHAPES[:3])
@pytest.mark.parametrize("operand", range(5), ids=OPERANDS)
def test_gradient_matches_the_recurrence(recurrent, chunk, per_block, operand):
    want = recurrent[3][operand]
    got = chunked_gradients(recurrent, chunk, per_block)[operand]
    assert np.all(np.isfinite(got))
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0
    np.testing.assert_allclose(got, want, atol=5e-5 * scale)


# ------------------------------------------------------------------ #
# both routes at lane-wide heads: d = 128, two heads, T = 70 a multiple of
# neither the chunk nor the block

WIDE = 128


def wide_operands(strength):
    return operands(strength, seed=4, heads=2, d=WIDE)


@pytest.fixture
def take_route(monkeypatch):
    """`take_route("kernel")` runs the Pallas kernels in interpret mode;
    "plain" is what the CPU takes anyway."""
    def take(route, form="channel"):
        if route == "kernel":
            monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
        assert dr.delta_rule_route((B, T, 2, WIDE), 16, 2, WIDE, form) == route
    return take


@pytest.fixture(scope="module", params=STRENGTHS)
def wide_recurrent(request):
    args = wide_operands(request.param)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    with jax.default_matmul_precision("highest"):
        values = jax.jit(dr.delta_rule_recurrent)(*args)
        grads = jax.jit(jax.grad(weighted(dr.delta_rule_recurrent, weight),
                                 argnums=range(5)))(*args)
    return args, weight, values, grads


@pytest.mark.parametrize("route", ["plain", "kernel"])
def test_values_match_the_recurrence_at_lane_wide_heads(wide_recurrent, take_route, route):
    take_route(route)
    args, _, (want, want_last), _ = wide_recurrent
    with jax.default_matmul_precision("highest"):
        got, last = jax.jit(lambda *a: chunked(16, 2)(*a))(*args)
    assert got.shape == want.shape and last.shape == want_last.shape
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(last, want_last, atol=2e-5)


_WIDE_GRADIENTS = {}


@pytest.mark.parametrize("route", ["plain", "kernel"])
@pytest.mark.parametrize("operand", range(5), ids=OPERANDS)
def test_gradient_matches_the_recurrence_at_lane_wide_heads(
        wide_recurrent, take_route, route, operand):
    take_route(route)
    args, weight, _, want = wide_recurrent
    key = (float(args[3][0, 0, 0, 0]), route)
    if key not in _WIDE_GRADIENTS:
        with jax.default_matmul_precision("highest"):
            _WIDE_GRADIENTS[key] = jax.jit(jax.grad(
                weighted(lambda *a: chunked(16, 2)(*a), weight), argnums=range(5)))(*args)
    got = _WIDE_GRADIENTS[key][operand]
    assert np.all(np.isfinite(got))
    scale = float(jnp.max(jnp.abs(want[operand])))
    assert scale > 0
    np.testing.assert_allclose(got, want[operand], atol=5e-5 * scale)


@pytest.mark.parametrize("strength", [1.0, 8.0])
@pytest.mark.parametrize("form", ["channel", "scalar"])
def test_the_kernel_route_takes_a_given_state_and_returns_the_last(take_route, form, strength):
    """From a state that is not zero, both results, the state's own gradient
    and g's — what reads Γ, which both routes take from
    `cumulative_log_decay` — as the plain route's, in both forms (the scalar
    one: the first channel's decay for the whole head)."""
    q, k, v, g, beta = wide_operands(strength)
    g = g if form == "channel" else g[..., 0]
    state = jax.random.normal(jax.random.PRNGKey(3), (B, 2, WIDE, WIDE))
    rule = lambda state, g: (lambda o, last: jnp.sum(o) + jnp.sum(last * last))(
        *dr.gated_delta_rule(q, k, v, g, beta, chunk=16, chunks_per_block=2,
                             compute_dtype=jnp.float32, initial_state=state))
    with jax.default_matmul_precision("highest"):
        take_route("plain", form)
        want = jax.jit(jax.value_and_grad(lambda s, g: rule(s, g), argnums=(0, 1)))(state, g)
        take_route("kernel", form)
        got = jax.jit(jax.value_and_grad(lambda s, g: rule(s, g), argnums=(0, 1)))(state, g)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        assert float(jnp.max(jnp.abs(b))) > 0
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.max(jnp.abs(b))))


def test_the_route_follows_the_head_width_and_what_can_run(monkeypatch):
    """Narrow heads are the plain body's whatever can run; lane-wide ones the
    kernels' where they can (interpret mode here), and the plain body's on
    the bare CPU, at a chunk that is not whole sub-blocks and at d_k ≠ d_v."""
    assert dr.delta_rule_route((B, T, H, 128), 64, 4) == "plain"
    monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
    assert dr.delta_rule_route((B, T, H, 8), 64, 4) == "plain"
    assert dr.delta_rule_route((B, T, H, 128), 64, 4) == "kernel"
    assert dr.delta_rule_route((B, T, H, 256), 16, 2) == "kernel"
    assert dr.delta_rule_route((B, T, H, 128), 6, 4) == "plain"
    assert dr.delta_rule_route((B, T, H, 128), 64, 4, v_dim=256) == "plain"


def test_the_overflow_case_is_one_a_quotient_form_fails():
    """What the strongest case guards: in a chunk of 64 the cumulative
    log-decay runs below −88, so `exp(−Γ)` is inf in float32 and a product
    with `exp(Γ)` = 0 is NaN — the differences the operator takes are not."""
    g = operands(8.0)[3]
    cum = jnp.cumsum(jnp.pad(g, ((0, 0), (0, 58), (0, 0), (0, 0))).reshape(B, 2, 64, H, D),
                     axis=2)
    assert float(jnp.min(cum)) < -88.0
    assert np.isinf(np.asarray(jnp.exp(-cum))).any()
    assert np.isnan(np.asarray(jnp.exp(cum) * jnp.exp(-cum))).any()


# Γ's own cases: (B, H, L, d) chunks at Kimi's kind of plane, at a narrow one
# and at the plain route's scalar view (…, L, 1)
GAMMA_SHAPES = [(2, 3, 64, 256), (2, 3, 64, 4), (2, 3, 16, 1)]


def gamma_operands(shape):
    """g in the overflow case's range (`operands(8.0)`: −8 · softplus(normal),
    so that Γ runs below −88 in a chunk of 64) and a cotangent."""
    keys = jax.random.split(jax.random.PRNGKey(11), 2)
    return (-8.0 * jax.nn.softplus(jax.random.normal(keys[0], shape)),
            jax.random.normal(keys[1], shape))


def float32_unit(terms):
    """The float32 unit a sum of a chunk's terms is held to: that of Σ |term|
    over the chunk, a (chunk, channel) — for g ≤ 0 the magnitude of Γ at the
    chunk's end."""
    return np.spacing(np.sum(np.abs(np.asarray(terms, np.float64)), axis=-2,
                             keepdims=True).astype(np.float32))


@pytest.mark.parametrize("shape", GAMMA_SHAPES, ids=str)
def test_gamma_is_the_running_sum_of_a_chunk(shape):
    """The triangular product against numpy's float64 cumulative sum, within
    eight float32 units of Γ's magnitude at the chunk's end (|Γ| ≈ 530): the
    same float32 additions in another order — 64 of them in a row read up to
    6.4 units over the 98 304 sums of the widest case and five seeds here, 4.1
    at four channels, where `jnp.cumsum`'s tree of them read 2.7 and 1.9; a
    bfloat16 Γ is 32 768 units off."""
    g, _ = gamma_operands(shape)
    want = np.cumsum(np.asarray(g, np.float64), axis=-2)
    got = jax.jit(dr.cumulative_log_decay)(g)
    assert got.dtype == jnp.float32 and got.shape == shape
    assert shape[-2] < 64 or want.min() < -88.0
    assert np.all(np.abs(np.asarray(got, np.float64) - want) <= 8 * float32_unit(g))


@pytest.mark.parametrize("shape", GAMMA_SHAPES, ids=str)
def test_gamma_pulls_back_to_the_reversed_running_sum(shape):
    """dg_i = Σ_{r ≥ i} dΓ_r, the transposed triangle's product: within four
    units of Σ |dΓ| over the chunk (terms of both signs: 2.2 read here)."""
    g, ct = gamma_operands(shape)
    want = np.flip(np.cumsum(np.flip(np.asarray(ct, np.float64), -2), axis=-2), -2)
    got, = jax.jit(lambda g, ct: jax.vjp(dr.cumulative_log_decay, g)[1](ct))(g, ct)
    assert got.dtype == jnp.float32 and got.shape == shape
    assert np.all(np.abs(np.asarray(got, np.float64) - want) <= 4 * float32_unit(ct))


@pytest.mark.parametrize("direction", ["forward", "pull-back"])
def test_gamma_is_a_product_and_no_windowed_reduction(direction):
    """What the chip is handed: a `dot_general` at the highest precision in
    either direction, and no `reduce_window` (XLA:TPU runs that one between two
    relayouts of the plane: PERF.md §6, PR 65)."""
    g = jnp.zeros((1, 4, 64, 256), jnp.float32)
    f = (dr.cumulative_log_decay if direction == "forward"
         else lambda ct: jax.vjp(dr.cumulative_log_decay, g)[1](ct)[0])
    text = jax.jit(f).lower(g).as_text()
    assert text.count("dot_general") == 1 and "HIGHEST" in text
    assert "reduce_window" not in text and "cumsum" not in text


def test_beta_zero_is_pure_decay():
    """Nothing is written: from a given state the output is the decayed state
    read by q, whatever k and v are."""
    q, k, v, g, _ = operands(0.3, t=40)
    state = jax.random.normal(jax.random.PRNGKey(3), (B, H, D, D))
    with jax.default_matmul_precision("highest"):
        o, last = dr.gated_delta_rule(q, k, v, g, jnp.zeros((B, 40, H)), chunk=16,
                                      chunks_per_block=2, compute_dtype=jnp.float32,
                                      initial_state=state)
    decay = jnp.exp(jnp.cumsum(g, axis=1))                              # (B, T, H, D)
    want = jnp.einsum("bthk,bhkv->bthv", q * decay, state, precision="highest")
    np.testing.assert_allclose(o, want, atol=1e-5)
    np.testing.assert_allclose(last, jnp.moveaxis(decay[:, -1], 1, 1)[..., None] * state,
                               atol=1e-5)


def test_no_decay_and_orthonormal_keys_is_a_pure_delta_rule():
    """g = 0, β = 1, keys from an orthonormal set: a key written twice stores
    its SECOND value, and reading a key returns what it last stored."""
    t, chunk = 2 * D, 4
    basis = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(0), (D, D)))[0]
    order = jnp.concatenate([jnp.arange(D), jnp.arange(D)])            # every key twice
    k = jnp.broadcast_to(basis[order][None, :, None, :], (1, t, 1, D))
    v = jax.random.normal(jax.random.PRNGKey(1), (1, t, 1, D))
    zeros, ones = jnp.zeros((1, t, 1, D)), jnp.ones((1, t, 1))
    with jax.default_matmul_precision("highest"):
        o, last = dr.gated_delta_rule(k, k, v, zeros, ones, chunk=chunk, chunks_per_block=2,
                                      compute_dtype=jnp.float32)
    np.testing.assert_allclose(o, v, atol=1e-5)          # a key reads what it just stored
    stored = jnp.einsum("kc,cv->kv", basis, last[0, 0], precision="highest")
    np.testing.assert_allclose(stored, v[0, D:, 0], atol=1e-5)         # the second values


@pytest.mark.parametrize("chunk", [16, 32])
def test_one_decay_a_head_without_the_erase_term_is_the_state_space_scan(chunk):
    """Where the two models coincide: the erase term off (β → 0 with v scaled
    by 1/β, so that β v is kept and β k kᵀ vanishes) and every channel of a
    head decaying alike, `S_t = a_t S_{t−1} + k_t v_tᵀ`, `o_t = S_tᵀ q_t` is
    Mamba-2's recurrence with x = v, B = k, C = q, Δ = 1 and A·Δ = g."""
    t, eps = 48, 1e-4
    q, k, v, g, _ = operands(0.5, t=t)
    g_head = g[..., :1]                                                # (B, T, H, 1)
    with jax.default_matmul_precision("highest"):
        got, _ = dr.gated_delta_rule(
            q, k, v / eps, jnp.broadcast_to(g_head, g.shape), jnp.full((B, t, H), eps),
            chunk=chunk, chunks_per_block=2, compute_dtype=jnp.float32)
        # ssd_chunked: a_t = exp(Δ_t · A) with A = −1 a head and Δ_t = −g_t;
        # its input is Δ_t · x_t, so x = v / Δ
        delta = -g_head[..., 0]                                        # (B, T, H) > 0
        want = ssm.ssd_chunked(v / delta[..., None], delta, -jnp.ones((H,)),
                               k, q, chunk=16, compute_dtype=jnp.float32)
    # the erase term is of order eps, not zero
    np.testing.assert_allclose(got, want, atol=2e-3 * float(jnp.max(jnp.abs(want))))


def test_the_inverse_is_the_inverse_and_pulls_back():
    n = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 20, 20)) * 0.3, -1)
    with jax.default_matmul_precision("highest"):
        x = dr.unit_lower_inverse(n)
        np.testing.assert_allclose(x @ (jnp.eye(20) - n), jnp.broadcast_to(jnp.eye(20), x.shape),
                                   atol=1e-5)
        weight = jax.random.normal(jax.random.PRNGKey(1), n.shape)
        got = jax.grad(lambda n: jnp.sum(dr.unit_lower_inverse(n) * weight))(n)
        want = jax.grad(lambda n: jnp.sum(jnp.linalg.inv(jnp.eye(20) - n) * weight))(n)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_a_chunk_is_whole_sub_blocks():
    with pytest.raises(ValueError, match="sub-blocks"):
        dr.gated_delta_rule(*operands(), chunk=20)


def test_a_recomputed_caller_keeps_the_named_residuals():
    """Under `jax.checkpoint(policy=KEEP_RESIDUALS)` the backward holds no
    second forward sweep: the output and the block-start states are saved by
    name."""
    args = operands(t=32)
    rule = lambda *a: chunked(16, 1)(*a)[0].sum()
    plain = jax.make_jaxpr(jax.grad(jax.checkpoint(rule)))(*args)
    kept = jax.make_jaxpr(jax.grad(jax.checkpoint(rule, policy=dr.KEEP_RESIDUALS)))(*args)
    scans = lambda jaxpr: str(jaxpr).count(" scan[")
    assert scans(kept) < scans(plain)


def test_a_recomputed_caller_of_the_kernel_route_runs_the_forward_kernel_once(take_route):
    """The kernel route names the same two arrays: recomputed without a
    policy the gradient holds the forward kernel twice, under
    `KEEP_RESIDUALS` once, beside the one backward kernel."""
    take_route("kernel")
    args = wide_operands(1.0)
    def calls(policy):
        jaxpr = jax.make_jaxpr(jax.grad(jax.checkpoint(
            lambda *a: chunked(16, 2)(*a)[0].sum(), policy=policy)))(*args)
        return [pallas_calls(jaxpr, name) for name in ("delta_rule_fwd", "delta_rule_bwd")]
    assert calls(None) == [2, 1]
    assert calls(dr.KEEP_RESIDUALS) == [1, 1]
