"""Pallas flash-attention kernel (ops/pallas_attention.py) vs the naive
reference, forward and backward, in interpret mode on CPU (the kernel's
compiled path needs a real TPU; numerics are identical by construction).
The banded grids are in `tests/test_pallas_attention_window.py`, the
backward's two routes and the `keep` plane in
`tests/test_pallas_attention_routes.py`."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops.attention import full_attention
from elasticdl_tpu.ops.pallas_attention import (
    KEEP_RESIDUALS,
    can_flash,
    flash_attention,
    pick_block,
)
from tests.conftest import equations, heavy_on_cpu, listening, pallas_calls

B, T, H, D = 2, 64, 2, 16


def _qkv(t_q=T, t_k=T, dtype=jnp.float32, seed=0):
    r = np.random.RandomState(seed)
    q = jnp.asarray(r.randn(B, t_q, H, D), dtype)
    k = jnp.asarray(r.randn(B, t_k, H, D), dtype)
    v = jnp.asarray(r.randn(B, t_k, H, D), dtype)
    return q, k, v


def test_pick_block():
    assert pick_block(64, 256) == 64
    assert pick_block(256, 256) == 256
    assert pick_block(512, 256) == 256
    assert pick_block(96, 256) == 32      # 96 = 32 * 3
    assert pick_block(100, 256) is None   # largest pow2 divisor is 4 < 8
    assert pick_block(4, 256) is None


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_naive(causal):
    q, k, v = _qkv()
    ref = full_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_naive(causal):
    q, k, v = _qkv()

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=16,
                              block_k=16, interpret=True)
        return jnp.sum(out ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_flash_offsets_position_causal_mask():
    """With q_offset/kv_offset the kernel masks against GLOBAL positions —
    the contract the Ulysses/ring callers rely on (cross-block case where
    the local q block sits after the kv block)."""
    q, k, v = _qkv(t_q=32, t_k=32, seed=1)
    # (16, 0) exercises partial masking within blocks; the others put the
    # whole kv block strictly before the q block. Fully-masked geometries
    # (e.g. kv entirely AFTER q) are covered by the dedicated test below —
    # there the naive path degenerates to uniform attention (finite NEG_BIG)
    # while flash returns 0; no real caller produces such rows.
    for q_off, kv_off in [(32, 0), (16, 0), (64, 32)]:
        ref = full_attention(q, k, v, causal=True,
                             q_offset=q_off, kv_offset=kv_off)
        got = flash_attention(q, k, v, causal=True, q_offset=q_off,
                              kv_offset=kv_off, block_q=16, block_k=16,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_flash_fully_masked_rows_are_zero_and_grads_finite():
    """A q block entirely BEFORE all kv (q_offset=0, kv_offset=T): every row
    is masked; forward must be 0 and backward must not NaN (the lse=-inf
    guard)."""
    q, k, v = _qkv(t_q=16, t_k=16, seed=2)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, q_offset=0,
                              kv_offset=1024, block_q=16, block_k=16,
                              interpret=True)
        return jnp.sum(out ** 2), out

    (l, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    assert np.all(np.asarray(out) == 0.0)
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g), 0.0, atol=1e-6)


def test_flash_bf16_inputs():
    q, k, v = _qkv(dtype=jnp.bfloat16, seed=3)
    ref = full_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2)


def test_flash_rectangular_and_uneven_blocks():
    """Tq != Tk, and a T whose best block is smaller than requested."""
    q, k, v = _qkv(t_q=32, t_k=96, seed=4)
    ref = full_attention(q, k, v, causal=False)
    got = flash_attention(q, k, v, causal=False, block_q=256, block_k=256,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_can_flash_gating(monkeypatch):
    from elasticdl_tpu.ops.pallas_attention import interpret_mode

    shp = (B, T, H, D)
    # CPU backend: off by default; EDL_FLASH=1 forces on ONLY where the
    # Mosaic kernel can actually run (TPU or interpret mode) — on plain
    # CPU/GPU it must stay off so full_attention falls back instead of
    # crashing in a backend with no Mosaic compile path; =0 forces off
    monkeypatch.delenv("EDL_FLASH", raising=False)
    assert can_flash(shp, shp) == (jax.default_backend() == "tpu")
    monkeypatch.setenv("EDL_FLASH", "1")
    assert can_flash(shp, shp) == (jax.default_backend() == "tpu")
    with interpret_mode():
        assert can_flash(shp, shp)
        assert can_flash(shp, shp, q_offset=jnp.int32(0))  # traced offsets OK
        assert not can_flash((B, 100, H, D), shp)          # unblockable T
    monkeypatch.setenv("EDL_FLASH", "0")
    with interpret_mode():
        assert not can_flash(shp, shp)


def test_interpret_active_survives_private_api_loss(monkeypatch, caplog):
    """ADVICE r4: _interpret_active leaned on the private
    jax._src.config.pallas_tpu_interpret_mode_context_manager attribute; a
    JAX rename must not silently disable flash routing. interpret_mode()
    now carries a public env signal, and a broken private probe logs a
    warning instead of failing silently."""
    import logging

    import jax._src.config as jax_config

    from elasticdl_tpu.ops import pallas_attention as pa

    # simulate a JAX upgrade that removed the private attribute
    monkeypatch.delattr(
        jax_config, "pallas_tpu_interpret_mode_context_manager",
        raising=False,
    )
    monkeypatch.setattr(pa, "_warned_probe_broken", False)
    monkeypatch.delenv(pa._INTERPRET_ENV, raising=False)

    # probe broken -> False, but LOUD (one warning). The package logger
    # does not propagate to root (default_logger sets that whenever it is
    # first called, which may be inside this very test), so caplog's
    # handler goes on the module's own logger.
    module_logger = logging.getLogger(pa.__name__)
    module_logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.WARNING, pa.__name__):
            assert pa._interpret_active() is False
            assert pa._interpret_active() is False  # warned once, not twice
    finally:
        module_logger.removeHandler(caplog.handler)
    assert sum(
        "interpret-mode probe" in r.getMessage() for r in caplog.records
    ) == 1

    # the public env signal keeps routing correct with the probe gone
    # (interpret_mode() sets it; set directly here because the real
    # force_tpu_interpret_mode also needs the deleted attribute)
    monkeypatch.setenv(pa._INTERPRET_ENV, "1")
    assert pa._interpret_active() is True


def test_interpret_mode_sets_and_restores_env_flag(monkeypatch):
    from elasticdl_tpu.ops import pallas_attention as pa

    monkeypatch.delenv(pa._INTERPRET_ENV, raising=False)
    with pa.interpret_mode():
        assert os.environ.get(pa._INTERPRET_ENV) == "1"
        assert pa._interpret_active() is True
    assert os.environ.get(pa._INTERPRET_ENV) is None  # restored on exit


def test_can_flash_bfloat16_tiling(monkeypatch):
    """bfloat16 Mosaic tiles are (16,128): a T whose largest pow-2 divisor
    is 8 blocks fine in float32 but must be refused in bfloat16 (it would
    fail to compile on real TPU — interpret mode can't catch that)."""
    from elasticdl_tpu.ops.pallas_attention import interpret_mode

    monkeypatch.setenv("EDL_FLASH", "1")
    shp24 = (B, 24, H, D)   # largest pow-2 divisor: 8
    shp32 = (B, 32, H, D)   # 32 >= 16: fine in both dtypes
    with interpret_mode():
        assert can_flash(shp24, shp24, dtype=jnp.float32)
        assert not can_flash(shp24, shp24, dtype=jnp.bfloat16)
        assert can_flash(shp32, shp32, dtype=jnp.bfloat16)


def test_full_attention_dispatches_to_flash(monkeypatch):
    """EDL_FLASH=1 + force_tpu_interpret_mode: full_attention routes through
    the kernel (the production TPU path, emulated) and matches the XLA
    fallback."""
    from elasticdl_tpu.ops.pallas_attention import interpret_mode

    q, k, v = _qkv(seed=5)
    monkeypatch.setenv("EDL_FLASH", "0")
    ref = full_attention(q, k, v, causal=True)
    monkeypatch.setenv("EDL_FLASH", "1")
    with interpret_mode():
        got = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_traced_offsets_match_static():
    """Offsets ride scalar prefetch, so traced values must behave exactly
    like Python ints — the contract ring attention depends on."""
    q, k, v = _qkv(t_q=32, t_k=32, seed=6)

    @jax.jit
    def with_traced(q, k, v, q_off, kv_off):
        return flash_attention(q, k, v, causal=True, q_offset=q_off,
                               kv_offset=kv_off, block_q=16, block_k=16,
                               interpret=True)

    for q_off, kv_off in [(32, 0), (16, 0), (64, 32)]:
        static = flash_attention(q, k, v, causal=True, q_offset=q_off,
                                 kv_offset=kv_off, block_q=16, block_k=16,
                                 interpret=True)
        traced = with_traced(q, k, v, jnp.int32(q_off), jnp.int32(kv_off))
        np.testing.assert_allclose(np.asarray(traced), np.asarray(static),
                                   atol=1e-6, rtol=1e-6)


def test_flash_lse_value_and_gradient():
    """flash_attention_lse: lse equals logsumexp of the masked scores, and
    gradients THROUGH lse are exact (the ring merge differentiates the
    combination weights, which folds g_lse into the kernel's delta)."""
    from elasticdl_tpu.ops.pallas_attention import flash_attention_lse

    q, k, v = _qkv(t_q=32, t_k=32, seed=7)

    def ref_lse(q, k):
        scale = q.shape[-1] ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        mask = jnp.arange(k.shape[1])[None, :] <= jnp.arange(q.shape[1])[:, None]
        s = jnp.where(mask[None, None], s, -1e30)
        return jax.scipy.special.logsumexp(s, axis=-1)     # (B, H, Tq)

    out, lse = flash_attention_lse(q, k, v, causal=True, block_q=16,
                                   block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse(q, k)),
                               atol=2e-5, rtol=2e-5)

    # a loss that uses BOTH outputs — compare against pure-XLA autodiff
    def loss_flash(q, k, v):
        out, lse = flash_attention_lse(q, k, v, causal=True, block_q=16,
                                       block_k=16, interpret=True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def loss_ref(q, k, v):
        return (jnp.sum(full_attention(q, k, v, causal=True) ** 2)
                + jnp.sum(jnp.sin(ref_lse(q, k))))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("causal", [
    pytest.param(False, marks=heavy_on_cpu), True,
])
def test_ring_flash_matches_full_attention(monkeypatch, causal):
    """Ring attention with the flash block kernel (EDL_FLASH=1 +
    force_tpu_interpret_mode on the data x seq CPU mesh) must match
    unsharded full attention, forward and backward — the lse merge and the
    traced-offset masking carry the whole correctness burden here."""
    from elasticdl_tpu.ops.pallas_attention import interpret_mode

    from elasticdl_tpu.ops.attention import sequence_parallel_attention
    from elasticdl_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"data": 2, "seq": 4})
    Bq, Tq, Hq, Dq = 2, 64, 2, 8          # local seq block = 16 rows
    r = np.random.RandomState(8)
    mk = lambda: jnp.asarray(r.randn(Bq, Tq, Hq, Dq), jnp.float32)
    q, k, v = mk(), mk(), mk()

    ref = full_attention(q, k, v, causal=causal)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(full_attention(q, k, v, causal=causal) ** 2),
        argnums=(0, 1, 2))(q, k, v)

    monkeypatch.setenv("EDL_FLASH", "1")
    with interpret_mode(), jax.set_mesh(mesh):
        got = jax.jit(
            lambda q, k, v: sequence_parallel_attention(
                q, k, v, causal=causal, mode="ring"))(q, k, v)
        g_got = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(sequence_parallel_attention(
                q, k, v, causal=causal, mode="ring") ** 2),
            argnums=(0, 1, 2)))(q, k, v)

    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    for a, b in zip(g_got, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_ulysses_flash_matches_full_attention(monkeypatch):
    """Ulysses + flash: the all-to-all re-shard hands each device the FULL
    sequence for H/n heads, and its local full_attention dispatches to the
    kernel (static offset 0) under EDL_FLASH=1 — must match unsharded
    attention forward and backward."""
    from elasticdl_tpu.ops.pallas_attention import interpret_mode

    from elasticdl_tpu.ops.attention import sequence_parallel_attention
    from elasticdl_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"data": 2, "seq": 4})
    Bq, Tq, Hq, Dq = 2, 64, 4, 8          # heads % seq_shards == 0
    r = np.random.RandomState(9)
    mk = lambda: jnp.asarray(r.randn(Bq, Tq, Hq, Dq), jnp.float32)
    q, k, v = mk(), mk(), mk()

    ref = full_attention(q, k, v, causal=True)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(full_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)

    monkeypatch.setenv("EDL_FLASH", "1")
    with interpret_mode(), jax.set_mesh(mesh):
        got = jax.jit(
            lambda q, k, v: sequence_parallel_attention(
                q, k, v, causal=True, mode="ulysses"))(q, k, v)
        g_got = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(sequence_parallel_attention(
                q, k, v, causal=True, mode="ulysses") ** 2),
            argnums=(0, 1, 2)))(q, k, v)

    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    for a, b in zip(g_got, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_flash_rejects_unblockable():
    q, k, v = _qkv(t_q=100, t_k=64)
    with pytest.raises(ValueError, match="cannot block"):
        flash_attention(q, k, v, interpret=True)


# ------------------------------------------------------------------ #
# grouped-query attention: fewer key-value heads than query heads


def _gqa(heads, kv_heads, seed=3):
    r = np.random.RandomState(seed)
    q = jnp.asarray(r.randn(B, T, heads, D), jnp.float32)
    k = jnp.asarray(r.randn(B, T, kv_heads, D), jnp.float32)
    v = jnp.asarray(r.randn(B, T, kv_heads, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("group", [1, 4, 16])
def test_grouped_query_fallback_is_attention_with_repeated_heads(group):
    """Query head i attends with key-value head i // group."""
    q, k, v = _gqa(16, 16 // group)
    want = full_attention(q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2))
    np.testing.assert_allclose(np.asarray(full_attention(q, k, v)), np.asarray(want),
                               atol=2e-6, rtol=2e-6)
    if 1 < group < 16:  # and NOT with head i % kv_heads (one kv head: the same)
        other = full_attention(q, jnp.tile(k, (1, 1, group, 1)), jnp.tile(v, (1, 1, group, 1)))
        assert float(jnp.max(jnp.abs(other - want))) > 1e-2


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("group", [1, 4, 16])
def test_grouped_query_flash_matches_fallback(group, direction):
    """The three kernels with K/V indexed by `h // group` against the XLA
    fallback; dK and dV are summed over the group's query heads inside the
    dkv kernel."""
    q, k, v = _gqa(16, 16 // group)
    flash = lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16,
                                            block_k=32, interpret=True)
    if direction == "forward":
        np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                                   np.asarray(full_attention(q, k, v)),
                                   atol=2e-5, rtol=2e-5)
        return
    probe = jnp.asarray(np.random.RandomState(4).randn(*q.shape), jnp.float32)
    g_ref = jax.grad(lambda *a: jnp.sum(probe * full_attention(*a)), argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(lambda *a: jnp.sum(probe * flash(*a)), argnums=(0, 1, 2))(q, k, v)
    assert g_got[1].shape == k.shape and g_got[2].shape == v.shape
    for a, b in zip(g_got, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


def test_grouped_query_heads_must_divide():
    q, k, v = _gqa(6, 4)
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    with pytest.raises(ValueError, match="do not divide"):
        full_attention(q, k, v)


# ------------------------------------------------------------------ #
# a head wider than the lane width (latent attention's 256)


@pytest.mark.parametrize("head,want", [
    (64, (1024, 1024)), (128, (1024, 1024)),        # today's answer, unchanged
    (192, (1024, 512)), (256, (1024, 512)), (512, (1024, 256))])
def test_the_block_plan_follows_the_head_size(head, want):
    """At most 128 wide the plan is what it was; a wider head takes a key
    block smaller in proportion (rounded down to a power of two that divides
    T): at 256 and (1024, 1024) the dq kernel does not fit the chip's VMEM."""
    from elasticdl_tpu.ops.pallas_attention import (
        DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, _plan_blocks)
    shape = (1, 8192, 20, head)
    assert _plan_blocks(shape, shape, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K,
                        dtype=jnp.bfloat16) == want
    # a short sequence is one block whatever the head
    short = (1, 64, 4, head)
    assert _plan_blocks(short, short, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K,
                        dtype=jnp.bfloat16) == (64, 64)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_flash_at_head_256_matches_the_fallback(direction):
    """The three kernels at the head size of latent attention, several key
    blocks a query block (the plan halves the key block asked for)."""
    r = np.random.RandomState(5)
    q, k, v = (jnp.asarray(r.randn(1, 64, 2, 256) * 0.5, jnp.float32) for _ in range(3))
    flash = lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=32,
                                            block_k=32, interpret=True)
    if direction == "forward":
        np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                                   np.asarray(full_attention(q, k, v)),
                                   atol=5e-5, rtol=5e-5)
        return
    probe = jnp.asarray(r.randn(*q.shape), jnp.float32)
    g_ref = jax.grad(lambda *a: jnp.sum(probe * full_attention(*a)), argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(lambda *a: jnp.sum(probe * flash(*a)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


# ------------------------------------------------------------------ #
# the kernels' residuals kept across a caller's recomputation


def _kept(f):
    return jax.checkpoint(f, policy=KEEP_RESIDUALS)


# (query heads, key-value heads, head size, block_k asked for, with_lse)
RECOMPUTED = {
    "head256": (2, 2, 256, 32, False),   # the plan halves the key block: (32, 16)
    "gqa32of2": (32, 2, 128, 16, False),
    "with_lse": (2, 2, 16, 16, True),
}


def _recomputed_layer(case):
    """(a new closure of a small "layer" — projections in front of the
    kernel, as a checkpointed block has them — returning a scalar loss; its
    arguments x, wq, wk). The loss weighs the logsumexp too where the case
    returns it."""
    from elasticdl_tpu.ops.pallas_attention import flash_attention_lse

    heads, kv_heads, head, block_k, with_lse = RECOMPUTED[case]
    r = np.random.RandomState(11)
    x = jnp.asarray(r.randn(1, 64, kv_heads, head) * 0.5, jnp.float32)
    wq = jnp.asarray(r.randn(head, heads // kv_heads * head) / head ** 0.5, jnp.float32)
    wk = jnp.asarray(r.randn(head, head) / head ** 0.5, jnp.float32)
    probe = jnp.asarray(r.randn(1, 64, heads, head), jnp.float32)
    probe_lse = jnp.asarray(r.randn(1, heads, 64), jnp.float32)

    def layer(x, wq, wk):
        q = (x @ wq).reshape(1, 64, heads, head)
        kw = dict(causal=True, block_q=32, block_k=block_k, interpret=True)
        if not with_lse:
            return flash_attention(q, x @ wk, x, **kw), None
        return flash_attention_lse(q, x @ wk, x, **kw)

    def loss(wrap):
        def f(x, wq, wk):
            out, lse = wrap(layer)(x, wq, wk)
            total = jnp.sum(probe * out)
            return (total if lse is None else total + jnp.sum(probe_lse * lse)), out
        return f

    return loss, (x, wq, wk)


@pytest.mark.parametrize("case", sorted(RECOMPUTED))
def test_kept_residuals_leave_one_forward_call_in_a_recomputed_layer(case):
    """The engagement counter: differentiating through a `jax.checkpoint`
    with `KEEP_RESIDUALS` holds ONE `flash_attention_fwd` call, through a
    plain one two, through none one."""
    loss, args = _recomputed_layer(case)
    calls = lambda wrap, kernel: pallas_calls(
        jax.make_jaxpr(jax.grad(loss(wrap), argnums=(0, 1, 2), has_aux=True))(
            *args).jaxpr, "flash_attention_" + kernel)
    assert calls(lambda f: f, "fwd") == 1
    assert calls(jax.checkpoint, "fwd") == 2
    assert [calls(_kept, kernel) for kernel in ("fwd", "bwd", "bwd_dq", "bwd_dkv")] == [1, 1, 0, 0]


@pytest.mark.parametrize("case", sorted(RECOMPUTED))
def test_kept_residuals_leave_no_q_k_v_to_rebuild(case):
    """All five residuals are the forward pass's own: the recomputation
    rebuilds neither projection (their backward wants x and the weights, not
    q and k). Two matmuls forward and four backward — dx and dw of each — in
    every form but the plain checkpoint's, which repeats the two."""
    loss, args = _recomputed_layer(case)
    matmuls = lambda wrap: equations(
        jax.make_jaxpr(jax.grad(loss(wrap), argnums=(0, 1, 2), has_aux=True))(
            *args).jaxpr, lambda eqn: eqn.primitive.name == "dot_general")
    assert [matmuls(wrap) for wrap in (lambda f: f, jax.checkpoint, _kept)] == [6, 8, 6]


@pytest.mark.parametrize("case", sorted(RECOMPUTED))
def test_kept_residuals_change_no_value_where_recomputing_is_exact(case):
    """out and the gradients under the policy are the un-checkpointed ones
    bit for bit, and the plain checkpoint's too. The second half is the CPU's
    property, where a recomputation repeats the forward pass to the bit: on a
    TPU the plain checkpoint differentiates a recomputed forward whose q, k, v
    are not the first one's in their last bits (PERF.md section 6, PR 35)."""
    loss, args = _recomputed_layer(case)
    run = lambda wrap: jax.jit(jax.grad(loss(wrap), argnums=(0, 1, 2), has_aux=True))(*args)
    want_grads, want_out = run(lambda f: f)
    for wrap in (jax.checkpoint, _kept):
        grads, out = run(wrap)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
        for got, want in zip(grads, want_grads):
            assert float(jnp.max(jnp.abs(want))) > 0
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("scale", [2.0, 4.0])
def test_residuals_of_two_forward_runs_do_not_mix(scale):
    """Why the policy keeps all five or none. A recomputation whose q and k
    come out one bfloat16 step off (as a TPU's do) and the backward kernels
    handed the FIRST run's out and logsumexp beside them: exp(q·k − lse) no
    longer sums to one along a row. Against the gradient at the first run's
    operands, that mixture lies half as far again or more in dq and dk, and two
    to five times as far in dv, as the gradient taken consistently at the
    perturbed operands — which is what a plain checkpoint computes."""
    from elasticdl_tpu.ops import pallas_attention as pa

    r = np.random.RandomState(21)
    mk = lambda s: jnp.asarray(r.randn(1, 2, 128, 64) * s, jnp.float32)     # (B, H, T, D)
    q, k, v, g = mk(scale), mk(scale), mk(1.0), mk(1.0)
    step = lambda x: x * (1 + 2.0 ** -8 * jnp.asarray(
        r.choice([-1.0, 0.0, 1.0], x.shape), jnp.float32))
    offs = jnp.zeros((2,), jnp.int32)
    plan = dict(causal=True, bq=32, bk=32, interpret=True)

    def residuals(q, k):
        return (offs, q, k, v) + tuple(pa._flash_fwd(offs, q, k, v, **plan))

    backward = lambda res: pa._flash_bwd(res, g.transpose(0, 2, 1, 3), None, **plan)[1:]
    first, second = residuals(q, k), residuals(step(q), step(k))
    want = backward(first)
    far = lambda got: [float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
                       for a, b in zip(got, want)]
    consistent, mixed = far(backward(second)), far(backward(second[:4] + first[4:]))
    assert all(x < 0.02 * scale for x in consistent), consistent
    assert all(m > 1.3 * c for m, c in zip(mixed, consistent)), (mixed, consistent)
    assert mixed[2] > scale * consistent[2]


@pytest.mark.parametrize("wrap", ["none", "plain", "kept"])
def test_logsumexp_carries_its_cotangent_through_a_recomputed_layer(wrap):
    """With a cotangent on the logsumexp ALONE the gradients are those of the
    XLA logsumexp of the masked scores, whatever keeps the residuals."""
    from elasticdl_tpu.ops.pallas_attention import flash_attention_lse

    q, k, v = _qkv(t_q=32, t_k=32, seed=12)
    probe = jnp.asarray(np.random.RandomState(13).randn(B, H, 32), jnp.float32)
    wrapped = {"none": lambda f: f, "plain": jax.checkpoint, "kept": _kept}[wrap]

    def lse_flash(q, k, v):
        lse = wrapped(lambda q, k, v: flash_attention_lse(
            q, k, v, causal=True, block_q=16, block_k=16, interpret=True)[1])(q, k, v)
        assert lse.shape == (B, H, 32) and lse.dtype == jnp.float32
        return jnp.sum(probe * lse)

    def lse_ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
        mask = jnp.arange(32)[None, :] <= jnp.arange(32)[:, None]
        return jnp.sum(probe * jax.scipy.special.logsumexp(
            jnp.where(mask[None, None], s, -1e30), axis=-1))

    got = jax.grad(lse_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lse_ref, argnums=(0, 1, 2))(q, k, v)
    assert float(jnp.max(jnp.abs(got[2]))) == 0.0      # v does not move the lse
    for a, b in zip(got[:2], want[:2]):
        assert float(jnp.max(jnp.abs(b))) > 1e-3
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


# ------------------------------------------------------------------ #
# what the window's and the routes' files share


def take_route(monkeypatch, route):
    """`bwd_route` reads the chip's VMEM: describe one with room for a head's
    k, v, dk and dv (a v5e's) or one with none. JAX keeps the trace of a
    custom rule's backward: a new rule for each route."""
    from elasticdl_tpu.ops import pallas_attention as pa

    pa._make_flash.cache_clear()
    monkeypatch.setattr(pa, "_vmem_bytes", lambda: {"resident": 128 << 20, "split": 1 << 10}[route])


@pytest.fixture
def bwd_log(caplog):
    """What `bwd_route` and `fwd_route` log, from empty caches: each logs once
    a shape."""
    from elasticdl_tpu.ops import pallas_attention as pa

    pa._bwd_plan.cache_clear()
    pa._fwd_plan.cache_clear()
    with listening(caplog, pa.__name__):
        yield caplog
