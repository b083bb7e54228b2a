"""Sliding-window attention, the banded grids of the flash kernels
(`ops/pallas_attention.py`, `flash_attention(..., window=W)`): against a
dense mask, in interpret mode on the CPU. A file of its own so that three
xdist workers share the kernel's cases (`tests/test_pallas_attention.py`,
`tests/test_pallas_attention_routes.py`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops.attention import full_attention
from elasticdl_tpu.ops.pallas_attention import can_flash, flash_attention
from tests.conftest import equations, pallas_calls
from tests.test_pallas_attention import D, _kept, take_route

# ------------------------------------------------------------------ #
# sliding-window attention: the banded grids


def _dense_window(q, k, v, window):
    """A dense-mask float32 computation, independent of `full_attention`:
    (out (B, T, H, D), logsumexp (B, H, T))."""
    t, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * q.shape[-1] ** -0.5
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    s = jnp.where((j <= i) & (j > i - window), s, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                     precision=jax.lax.Precision.HIGHEST)
    return out, jax.nn.logsumexp(s, axis=-1)


def _windowed_case(t, heads, kv_heads, seed=7):
    r = np.random.RandomState(seed)
    draw = lambda h: jnp.asarray(r.randn(1, t, h, D), jnp.float32)
    return draw(heads), draw(kv_heads), draw(kv_heads)


# a block is 16 here: W in {1, 5, a block, a block -+ 1, >= T}
WINDOWS = [1, 5, 15, 16, 17, 40, 96, 200]
# (T, H, Hkv, block_q, block_k): T not a multiple of W, bq != bk, groups 1, 4, 8
GEOMETRIES = [(96, 2, 2, 16, 16), (96, 4, 1, 32, 16), (96, 8, 1, 16, 32)]


@pytest.mark.parametrize("geometry", GEOMETRIES, ids=lambda g: "T%d-H%d/%d-b%dx%d" % g)
@pytest.mark.parametrize("window", WINDOWS)
def test_windowed_forward_and_logsumexp_match_a_dense_mask(window, geometry):
    from elasticdl_tpu.ops.pallas_attention import flash_attention_lse

    t, heads, kv_heads, bq, bk = geometry
    q, k, v = _windowed_case(t, heads, kv_heads)
    out, lse = flash_attention_lse(q, k, v, window=window, block_q=bq, block_k=bk,
                                   interpret=True)
    want, want_lse = _dense_window(q, k, v, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("geometry", GEOMETRIES, ids=lambda g: "T%d-H%d/%d-b%dx%d" % g)
@pytest.mark.parametrize("window", [1, 5, 16, 17, 40])
def test_windowed_gradients_match_a_dense_mask(window, geometry):
    """dq, dk, dv of both outputs (the logsumexp's cotangent too), through the
    banded dq and dkv kernels."""
    from elasticdl_tpu.ops.pallas_attention import flash_attention_lse

    t, heads, kv_heads, bq, bk = geometry
    q, k, v = _windowed_case(t, heads, kv_heads)
    r = np.random.RandomState(8)
    probe = jnp.asarray(r.randn(1, t, heads, D), jnp.float32)
    probe_lse = jnp.asarray(r.randn(1, heads, t), jnp.float32)
    weigh = lambda f: lambda *a: (lambda out, lse: jnp.sum(probe * out)
                                  + jnp.sum(probe_lse * lse))(*f(*a))
    got = jax.grad(weigh(lambda *a: flash_attention_lse(
        *a, window=window, block_q=bq, block_k=bk, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(weigh(lambda *a: _dense_window(*a, window)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("window", [96, 97, 4096])
def test_a_window_of_the_whole_length_is_the_causal_call_to_the_bit(window):
    q, k, v = _windowed_case(96, 4, 2)
    kw = dict(block_q=16, block_k=32, interpret=True)
    f = lambda window: (lambda *a: jnp.sum(flash_attention(*a, window=window, **kw) ** 2))
    np.testing.assert_array_equal(np.asarray(flash_attention(q, k, v, window=window, **kw)),
                                  np.asarray(flash_attention(q, k, v, **kw)))
    for a, b in zip(jax.grad(f(window), argnums=(0, 1, 2))(q, k, v),
                    jax.grad(f(None), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    names = _kernel_grids(jax.make_jaxpr(jax.grad(f(window)))(q, k, v).jaxpr)
    assert sorted(names) == ["flash_attention_bwd", "flash_attention_fwd"]


def _kernel_grids(jaxpr):
    """{kernel name: grid} of every pallas_call of a jaxpr."""
    out = {}

    def note(eqn):
        if eqn.primitive.name == "pallas_call":
            out[eqn.params["name"]] = tuple(eqn.params["grid_mapping"].grid)

    equations(jaxpr, note)
    return out


@pytest.mark.parametrize("route,window,want", [
    # one call each way: (B, key-value heads, 6 q blocks x 4 heads a group)
    ("resident", None, {"flash_attention_fwd": (1, 2, 24), "flash_attention_bwd": (1, 2, 24)}),
    # W = a block: 2 kv blocks a q block; the resident kernels' grids do not
    # band, their loops over a q block's kv blocks do
    ("resident", 16, {"flash_attention_swa_fwd": (1, 2, 24),
                      "flash_attention_swa_bwd": (1, 2, 24)}),
    ("resident", 40, {"flash_attention_swa_fwd": (1, 2, 24),
                      "flash_attention_swa_bwd": (1, 2, 24)}),
    ("resident", 32, {"flash_attention_swa_fwd": (1, 2, 24),
                      "flash_attention_swa_bwd": (1, 2, 24)}),
    # a head that does not fit: the streaming forward, the dq and the dkv kernel
    ("split", None, {"flash_attention_fwd": (1, 8, 6, 6), "flash_attention_bwd_dq": (1, 8, 6, 6),
                     "flash_attention_bwd_dkv": (1, 2, 6, 24)}),
    # W = a block: 2 kv blocks a q block, 2 q blocks a kv block (x 4 heads a group)
    ("split", 16, {"flash_attention_swa_fwd": (1, 8, 6, 2), "flash_attention_swa_bwd_dq": (1, 8, 6, 2),
                   "flash_attention_swa_bwd_dkv": (1, 2, 6, 8)}),
    ("split", 40, {"flash_attention_swa_fwd": (1, 8, 6, 4), "flash_attention_swa_bwd_dq": (1, 8, 6, 4),
                   "flash_attention_swa_bwd_dkv": (1, 2, 6, 16)}),
    # W = two blocks (trinity-mini's 2048 over 1024-blocks): 3 kv blocks a q block
    ("split", 32, {"flash_attention_swa_fwd": (1, 8, 6, 3), "flash_attention_swa_bwd_dq": (1, 8, 6, 3),
                   "flash_attention_swa_bwd_dkv": (1, 2, 6, 12)}),
])
def test_the_grid_is_banded_under_a_window_and_as_it_was_without(route, window, want, monkeypatch):
    """Read off the lowered calls: `window=None` keeps the unbanded grid and
    the plain kernel names; a window shortens the kv axis of the streaming
    forward's grid (and of the split route's dq grid, and the q axis of its dkv
    grid), under names of their own; the resident kernels' grids have no kv
    axis."""
    take_route(monkeypatch, route)
    q, k, v = _windowed_case(96, 8, 2)
    f = lambda *a: jnp.sum(flash_attention(*a, window=window, block_q=16, block_k=16,
                                           interpret=True) ** 2)
    assert _kernel_grids(jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v).jaxpr) == want


@pytest.mark.parametrize("t,window,blocks,want", [
    (16384, 1024, (1024, 1024), (31, 136)),      # the benchmark's cell, 1024-blocks
    (16384, 1024, (1024, 512), (62, 272)),     # 4 kv blocks of 512 a q block, not 3
    (16384, None, (1024, 1024), (136, 136)),
    # trinity-mini.resident-16k: W = two blocks, three kv blocks a q block
    # (one edge half masked, one whole, the diagonal), 67% of the pairs visible
    (16384, 2048, (1024, 1024), (45, 136)),
    (16384, 2048, (1024, 512), (90, 272)),
    (16384, 2047, (1024, 1024), (45, 136)),      # one key fewer: the same blocks
    (16384, 2049, (1024, 1024), (45, 136)),      # one key more: the third block's first key
    (16384, 2050, (1024, 1024), (58, 136)),      # two more: a fourth block's corner
    (96, 16, (16, 16), (11, 21)),
    (100, 16, (16, 16), (0, 0)),                 # cannot be blocked
])
def test_kv_block_visits_counts_the_blocks_that_compute(monkeypatch, t, window, blocks, want):
    from elasticdl_tpu.ops import pallas_attention as pa

    for name, value in zip(("DEFAULT_BLOCK_Q", "DEFAULT_BLOCK_K"), blocks):
        monkeypatch.setattr(pa, name, value)
    assert pa.kv_block_visits(t, t, window) == want


def test_windowed_layer_under_checkpoint_keeps_one_forward():
    """`KEEP_RESIDUALS` holds for a windowed call: ONE `flash_attention_swa_fwd`
    in a recomputed layer's gradient, two under a plain `jax.checkpoint`, and
    the same values."""
    r = np.random.RandomState(12)
    x = jnp.asarray(r.randn(1, 64, 2, 16) * 0.5, jnp.float32)
    w = jnp.asarray(r.randn(16, 4 * 16) / 4, jnp.float32)

    def loss(wrap):
        def layer(x, w):
            q = (x @ w).reshape(1, 64, 8, 16)
            return flash_attention(q, x, x, window=24, block_q=16, block_k=16, interpret=True)
        return lambda x, w: jnp.sum(wrap(layer)(x, w) ** 2)

    calls = lambda wrap, kernel: pallas_calls(
        jax.make_jaxpr(jax.grad(loss(wrap), argnums=(0, 1)))(x, w).jaxpr,
        "flash_attention_swa_" + kernel)
    assert calls(jax.checkpoint, "fwd") == 2
    assert [calls(_kept, kernel) for kernel in ("fwd", "bwd", "bwd_dq", "bwd_dkv")] == [1, 1, 0, 0]
    for a, b in zip(jax.grad(loss(_kept), argnums=(0, 1))(x, w),
                    jax.grad(loss(lambda f: f), argnums=(0, 1))(x, w)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_window_takes_no_offsets_and_no_acausal_mask(monkeypatch):
    q, k, v = _windowed_case(64, 2, 2)
    with pytest.raises(ValueError, match="unsharded"):
        flash_attention(q, k, v, window=8, q_offset=64, interpret=True)
    with pytest.raises(ValueError, match="unsharded"):
        flash_attention(q, k, v, window=8, kv_offset=jnp.int32(0), interpret=True)
    with pytest.raises(ValueError, match="CAUSAL"):
        flash_attention(q, k, v, window=8, causal=False, interpret=True)
    with pytest.raises(ValueError, match="at least itself"):
        flash_attention(q, k, v, window=0, interpret=True)
    # and `can_flash` declines one, so `full_attention` takes its XLA path
    monkeypatch.setenv("EDL_FLASH", "1")
    monkeypatch.setenv("EDL_FLASH_INTERPRET", "1")
    assert can_flash(q.shape, k.shape, window=8)
    assert not can_flash(q.shape, k.shape, q_offset=64, window=8)
    assert not can_flash(q.shape, k.shape, kv_offset=jnp.int32(0), window=8)
    assert can_flash(q.shape, k.shape, q_offset=64)
    got = full_attention(q, k, v, q_offset=64, kv_offset=32, window=40)
    jaxpr = jax.make_jaxpr(lambda *a: full_attention(*a, q_offset=64, kv_offset=32,
                                                     window=40))(q, k, v).jaxpr
    assert not _kernel_grids(jaxpr)
    assert got.shape == q.shape


def test_full_attention_passes_its_window_to_the_kernel(monkeypatch):
    monkeypatch.setenv("EDL_FLASH", "1")
    monkeypatch.setenv("EDL_FLASH_INTERPRET", "1")
    q, k, v = _windowed_case(64, 4, 2)
    jaxpr = jax.make_jaxpr(lambda *a: full_attention(*a, window=8))(q, k, v).jaxpr
    assert pallas_calls(jaxpr, "flash_attention_swa_fwd") == 1
    np.testing.assert_allclose(np.asarray(full_attention(q, k, v, window=8)),
                               np.asarray(_dense_window(q, k, v, 8)[0]), atol=2e-5, rtol=2e-5)
