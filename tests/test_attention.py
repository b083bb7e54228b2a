"""Sequence-parallel attention (ops/attention.py): ring and Ulysses must
match full attention bitwise-close, forward and backward, causal and not —
on a (data x seq) CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops.attention import (
    full_attention,
    sequence_parallel_attention,
)
from elasticdl_tpu.parallel.mesh import build_mesh

B, T, H, D = 2, 32, 4, 8


@pytest.fixture(scope="module")
def qkv():
    r = np.random.RandomState(0)
    mk = lambda: jnp.asarray(r.randn(B, T, H, D), jnp.float32)
    return mk(), mk(), mk()


@pytest.fixture(scope="module")
def seq_mesh():
    return build_mesh({"data": 2, "seq": 4})


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("mode", ["ring", "ulysses"])
def test_matches_full_attention(qkv, seq_mesh, causal, mode):
    q, k, v = qkv
    ref = full_attention(q, k, v, causal=causal)
    with jax.set_mesh(seq_mesh):
        out = jax.jit(
            lambda q, k, v: sequence_parallel_attention(
                q, k, v, causal=causal, mode=mode
            )
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_gradients_match(qkv, seq_mesh, causal):
    q, k, v = qkv

    def ref_loss(q, k, v):
        return (full_attention(q, k, v, causal=causal) ** 2).sum()

    def ring_loss(q, k, v):
        return (
            sequence_parallel_attention(q, k, v, causal=causal, mode="ring") ** 2
        ).sum()

    g_ref = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
    with jax.set_mesh(seq_mesh):
        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-4)


def test_falls_back_without_seq_axis(qkv):
    q, k, v = qkv
    mesh = build_mesh({"data": 8})
    ref = full_attention(q, k, v, causal=True)
    with jax.set_mesh(mesh):
        out = jax.jit(
            lambda q, k, v: sequence_parallel_attention(q, k, v, causal=True)
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_causal_offsets_position_blocks():
    """full_attention's q/kv offsets reproduce a slice of global attention —
    the primitive the ring schedule builds on."""
    r = np.random.RandomState(1)
    q = jnp.asarray(r.randn(1, 8, 2, 4), jnp.float32)
    k = jnp.asarray(r.randn(1, 8, 2, 4), jnp.float32)
    v = jnp.asarray(r.randn(1, 8, 2, 4), jnp.float32)
    whole = full_attention(q, k, v, causal=True)
    # second half of q attending over the FULL kv with its true position
    part = full_attention(q[:, 4:], k, v, causal=True, q_offset=4)
    np.testing.assert_allclose(np.asarray(part), np.asarray(whole[:, 4:]), atol=1e-6)


def test_ulysses_rejects_indivisible_heads(seq_mesh):
    r = np.random.RandomState(2)
    x = jnp.asarray(r.randn(B, T, 3, D), jnp.float32)  # 3 heads, 4 shards
    with jax.set_mesh(seq_mesh):
        with pytest.raises(Exception, match="divisible|heads"):
            jax.jit(
                lambda q, k, v: sequence_parallel_attention(
                    q, k, v, mode="ulysses"
                )
            )(x, x, x)


# ------------------------------------------------------------------ #
# sliding-window attention on the XLA path


def _dense_window(q, k, v, window, q_offset=0, kv_offset=0):
    """Softmax over the keys j with i - window < j <= i, written out densely
    from GLOBAL positions, key-value heads repeated for their groups."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    i = (q_offset + jnp.arange(q.shape[1]))[:, None]
    j = (kv_offset + jnp.arange(k.shape[1]))[None, :]
    s = jnp.where((j <= i) & (j > i - window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def _heads(t, heads, kv_heads, seed=0):
    r = np.random.RandomState(seed)
    draw = lambda h: jnp.asarray(r.randn(2, t, h, 8), jnp.float32)
    return draw(heads), draw(kv_heads), draw(kv_heads)


@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (4, 1), (8, 1), (8, 2)])
@pytest.mark.parametrize("window", [1, 5, 15, 16, 17, 40, 64])
def test_windowed_fallback_matches_a_dense_mask(window, heads, kv_heads):
    """`full_attention`'s XLA path and `_grouped_query_attention` (fewer
    key-value heads) under a window, T = 40 not a multiple of it."""
    q, k, v = _heads(40, heads, kv_heads)
    np.testing.assert_allclose(np.asarray(full_attention(q, k, v, window=window)),
                               np.asarray(_dense_window(q, k, v, window)), atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (8, 2)])
def test_windowed_fallback_gradients_match_a_dense_mask(heads, kv_heads):
    q, k, v = _heads(40, heads, kv_heads, seed=1)
    probe = jnp.asarray(np.random.RandomState(2).randn(*q.shape), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(probe * full_attention(*a, window=7)),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(probe * _dense_window(*a, 7)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (4, 2)])
def test_windowed_fallback_positions_blocks_by_their_offsets(heads, kv_heads):
    """A sequence-parallel caller's local blocks: the window counts GLOBAL
    positions (the q block starts 24 after the kv block; window 30 reaches
    back into it, and every row sees at least one key)."""
    q, k, v = _heads(16, heads, kv_heads, seed=3)
    got = full_attention(q, k, v, q_offset=40, kv_offset=16, window=30)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_dense_window(q, k, v, 30, 40, 16)),
                               atol=2e-6, rtol=2e-6)


def test_a_window_of_the_whole_length_is_causal_attention():
    q, k, v = _heads(40, 4, 2, seed=4)
    np.testing.assert_array_equal(np.asarray(full_attention(q, k, v, window=40)),
                                  np.asarray(full_attention(q, k, v)))


def test_a_window_needs_a_causal_mask():
    q, k, v = _heads(16, 2, 2)
    with pytest.raises(ValueError, match="CAUSAL"):
        full_attention(q, k, v, causal=False, window=4)


# ------------------------------------------------------------------ #
# a mask that is data (`keep`), in the XLA path


def _dense_keep(q, k, v, keep):
    b, t, h, d = q.shape
    k, v = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    mask = jnp.tril(jnp.ones((t, t), bool))[None] & keep
    s = jnp.where(mask[:, None], s, -jnp.inf)
    return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v),
            jax.nn.logsumexp(s, axis=-1))


def _keep(t, seed=6):
    r = np.random.RandomState(seed)
    return jnp.asarray((r.rand(2, t, t) < 0.4) | np.eye(t, dtype=bool)[None])


@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (4, 1), (8, 2)])
@pytest.mark.parametrize("dtype", ["bool", "int8"])
def test_keep_fallback_matches_a_dense_mask(heads, kv_heads, dtype):
    """`full_attention`'s XLA path and `_grouped_query_attention` under a data
    mask, output and logsumexp."""
    q, k, v = _heads(40, heads, kv_heads, seed=5)
    keep = _keep(40)
    got = full_attention(q, k, v, keep=keep.astype(dtype), with_lse=True)
    for a, b in zip(got, _dense_keep(q, k, v, keep)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6, rtol=2e-6)
    np.testing.assert_array_equal(np.asarray(full_attention(q, k, v, keep=keep)),
                                  np.asarray(got[0]))


@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (8, 2)])
def test_keep_fallback_gradients_match_a_dense_mask(heads, kv_heads):
    q, k, v = _heads(40, heads, kv_heads, seed=7)
    keep = _keep(40)
    probe = jnp.asarray(np.random.RandomState(2).randn(*q.shape), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(probe * full_attention(*a, keep=keep)),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(probe * _dense_keep(*a, keep)[0]), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6, rtol=2e-6)


def test_keep_of_all_ones_is_causal_attention_and_joins_a_window():
    q, k, v = _heads(40, 4, 2, seed=8)
    ones = jnp.ones((2, 40, 40), bool)
    np.testing.assert_array_equal(np.asarray(full_attention(q, k, v, keep=ones)),
                                  np.asarray(full_attention(q, k, v)))
    np.testing.assert_array_equal(np.asarray(full_attention(q, k, v, keep=ones, window=7)),
                                  np.asarray(full_attention(q, k, v, window=7)))
