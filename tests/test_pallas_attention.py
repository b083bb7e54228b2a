"""Pallas flash-attention kernel (ops/pallas_attention.py) vs the naive
reference, forward and backward, in interpret mode on CPU (the kernel's
compiled path needs a real TPU; numerics are identical by construction)."""

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
    # one backward call: (B, key-value heads, 4 heads a group x 6 q blocks)
    ("resident", None, {"flash_attention_fwd": (1, 8, 6, 6), "flash_attention_bwd": (1, 2, 24)}),
    # W = a block: 2 kv blocks a q block; the backward's grid does not band,
    # its loop over a q block's kv blocks does
    ("resident", 16, {"flash_attention_swa_fwd": (1, 8, 6, 2),
                      "flash_attention_swa_bwd": (1, 2, 24)}),
    ("resident", 40, {"flash_attention_swa_fwd": (1, 8, 6, 4),
                      "flash_attention_swa_bwd": (1, 2, 24)}),
    ("split", None, {"flash_attention_fwd": (1, 8, 6, 6), "flash_attention_bwd_dq": (1, 8, 6, 6),
                     "flash_attention_bwd_dkv": (1, 2, 6, 24)}),
    # W = a block: 2 kv blocks a q block, 2 q blocks a kv block (x 4 heads a group)
    ("split", 16, {"flash_attention_swa_fwd": (1, 8, 6, 2), "flash_attention_swa_bwd_dq": (1, 8, 6, 2),
                   "flash_attention_swa_bwd_dkv": (1, 2, 6, 8)}),
    ("split", 40, {"flash_attention_swa_fwd": (1, 8, 6, 4), "flash_attention_swa_bwd_dq": (1, 8, 6, 4),
                   "flash_attention_swa_bwd_dkv": (1, 2, 6, 16)}),
])
def test_the_grid_is_banded_under_a_window_and_as_it_was_without(route, window, want, monkeypatch):
    """Read off the lowered calls: `window=None` keeps the unbanded grid and
    the plain kernel names; a window shortens the kv axis of the forward grid
    (and of the split route's dq grid, and the q axis of its dkv grid), under
    names of their own."""
    take_route(monkeypatch, route)
    q, k, v = _windowed_case(96, 8, 2)
    f = lambda *a: jnp.sum(flash_attention(*a, window=window, block_q=16, block_k=16,
                                           interpret=True) ** 2)
    assert _kernel_grids(jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v).jaxpr) == want


@pytest.mark.parametrize("t,window,blocks,want", [
    (16384, 1024, (1024, 1024), (31, 136)),      # the benchmark's cell, 1024-blocks
    (16384, 1024, (1024, 512), (62, 272)),     # 4 kv blocks of 512 a q block, not 3
    (16384, None, (1024, 1024), (136, 136)),
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



# ------------------------------------------------------------------ #
# the backward's two routes


def take_route(monkeypatch, route):
    """`bwd_route` reads the chip's VMEM: describe one with room for a head's
    k, v, dk and dv (a v5e's) or one with none. JAX keeps the trace of a
    custom rule's backward: a new rule for each route."""
    from elasticdl_tpu.ops import pallas_attention as pa

    pa._make_flash.cache_clear()
    monkeypatch.setattr(pa, "_vmem_bytes", lambda: {"resident": 128 << 20, "split": 1 << 10}[route])


@pytest.fixture
def bwd_log(caplog):
    """What `bwd_route` logs, from an empty cache: it logs once a shape."""
    from elasticdl_tpu.ops import pallas_attention as pa

    pa._bwd_plan.cache_clear()
    with listening(caplog, pa.__name__):
        yield caplog


# (T, heads, key-value heads, head size, block_q, block_k, causal, window,
#  (q_offset, kv_offset), with a cotangent on the logsumexp)
BACKWARD = {
    "mha": (64, 2, 2, 16, 16, 16, True, None, (0, 0), False),
    "acausal": (64, 2, 2, 16, 16, 32, False, None, (0, 0), False),
    "one_block": (32, 2, 2, 16, 32, 32, True, None, (0, 0), False),     # ONE kv block a q block
    "group16": (64, 32, 2, 16, 32, 16, True, None, (0, 0), True),        # Nemotron's 32 on 2
    "head256": (64, 2, 1, 256, 32, 16, True, None, (0, 0), False),       # two diagonal blocks
    "window_in_a_block": (96, 4, 2, 16, 32, 32, True, 5, (0, 0), True),  # the band in ONE kv block
    "window_a_block": (96, 4, 1, 16, 16, 16, True, 16, (0, 0), False),
    "window_off_block": (96, 8, 2, 16, 16, 32, True, 40, (0, 0), True),  # whole blocks in the band
    "window_wide": (128, 2, 2, 16, 16, 16, True, 50, (0, 0), False),
    "offsets": (64, 2, 2, 16, 16, 16, True, None, (64, 32), True),       # ring attention's
    "offsets_before": (64, 2, 2, 16, 16, 16, True, None, (0, 48), True), # q blocks seeing NO key
    "offsets_unaligned": (64, 4, 2, 16, 32, 16, True, None, (40, 8), False),
}


def _backward_case(name):
    from elasticdl_tpu.ops.pallas_attention import flash_attention_lse

    t, heads, kv_heads, head, bq, bk, causal, window, (q_off, kv_off), with_lse = BACKWARD[name]
    r = np.random.RandomState(31)
    draw = lambda *shape: jnp.asarray(r.randn(*shape) * 0.5, jnp.float32)
    q, k, v = draw(1, t, heads, head), draw(1, t, kv_heads, head), draw(1, t, kv_heads, head)
    probe, probe_lse = draw(1, t, heads, head), draw(1, heads, t) * float(with_lse)

    def weigh(out, lse):
        return jnp.sum(probe * out) + jnp.sum(probe_lse * jnp.where(lse > -1e29, lse, 0.0))

    def flash(q, k, v):
        # traced offsets, as ring attention passes them (a window takes none)
        offsets = {} if window is not None else dict(
            q_offset=jnp.int32(q_off), kv_offset=jnp.int32(kv_off))
        if with_lse:
            return weigh(*flash_attention_lse(q, k, v, causal=causal, window=window, block_q=bq,
                                              block_k=bk, interpret=True, **offsets))
        return jnp.sum(probe * flash_attention(q, k, v, causal=causal, window=window, block_q=bq,
                                               block_k=bk, interpret=True, **offsets))

    def dense(q, k, v):
        group = heads // kv_heads
        kk, vv = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                       precision=jax.lax.Precision.HIGHEST) * head ** -0.5
        i, j = q_off + jnp.arange(t)[:, None], kv_off + jnp.arange(t)[None, :]
        mask = (j <= i) if causal else jnp.ones((t, t), bool)
        if window is not None:
            mask &= j > i - window
        s = jnp.where(mask, s, -jnp.inf)
        rows = jnp.any(mask, axis=1)[None, None, :, None]        # a row with no key: zeros
        p = jnp.where(rows, jax.nn.softmax(jnp.where(rows, s, 0.0), axis=-1), 0.0)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, vv, precision=jax.lax.Precision.HIGHEST)
        lse = jnp.where(rows[..., 0], jax.nn.logsumexp(jnp.where(rows, s, 0.0), axis=-1), 0.0)
        return weigh(out, lse)

    return flash, dense, (q, k, v)


@pytest.mark.parametrize("name", sorted(BACKWARD))
def test_the_resident_backward_is_the_split_one_to_the_bit(name, monkeypatch):
    """dq, dk, dv by the one kernel — a head's k and v resident, a pair's
    score block computed once — against a dense mask, and against the dq and
    dkv kernels bit for bit: the same operands, the same order of sums."""
    flash, dense, args = _backward_case(name)
    got = {}
    for route in ("resident", "split"):
        take_route(monkeypatch, route)
        jaxpr = jax.make_jaxpr(jax.grad(flash, argnums=(0, 1, 2)))(*args).jaxpr
        names = sorted(n.replace("swa_", "") for n in _kernel_grids(jaxpr))
        assert names == {"resident": ["flash_attention_bwd", "flash_attention_fwd"],
                         "split": ["flash_attention_bwd_dkv", "flash_attention_bwd_dq",
                                   "flash_attention_fwd"]}[route]
        got[route] = jax.grad(flash, argnums=(0, 1, 2))(*args)
    want = jax.grad(dense, argnums=(0, 1, 2))(*args)
    for a, b, c in zip(got["resident"], got["split"], want):
        assert float(jnp.max(jnp.abs(c))) > 1e-3
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-4, rtol=1e-4)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# (keys, head, dtype) -> the blocks the plan gives and the route on a v5e's
# 128 MiB of VMEM: the four language-model cells of BENCHMARK.json, then what
# does not fit
ROUTES = {
    "olmoe-1b-7b.resident-4k": (4096, 128, jnp.bfloat16, "resident"),
    "nemotron-3-nano-30b-a3b.resident-8k": (8192, 128, jnp.bfloat16, "resident"),
    "glm-4.7-flash.resident-8k": (8192, 256, jnp.bfloat16, "resident"),
    "mellum2-12b-a2.5b.resident-16k": (16384, 128, jnp.bfloat16, "resident"),
    "32k_keys": (32768, 128, jnp.bfloat16, "split"),
    "16k_keys_of_256": (16384, 256, jnp.bfloat16, "split"),
    "16k_keys_float32": (16384, 128, jnp.float32, "split"),
}


@pytest.mark.parametrize("name", sorted(ROUTES))
def test_the_backward_route_follows_the_head_s_bytes_and_logs_once_a_shape(
        name, bwd_log, monkeypatch):
    from elasticdl_tpu.ops import pallas_attention as pa

    take_route(monkeypatch, "resident")            # a described v5e
    t_k, head, dtype, want = ROUTES[name]
    shape = (1, t_k, 4, head)
    bq, bk = pa._plan_blocks(shape, shape, None, None, dtype=dtype)
    plan = pa.bwd_route(t_k, head, dtype, bq, bk)
    assert plan.route == want
    assert (plan.vmem_bytes <= plan.vmem_limit) == (want == "resident")
    assert plan.vmem_limit == (128 << 20) * 3 // 4
    # k, v, dk, dv twice buffered and the two float32 accumulators at least
    assert plan.vmem_bytes > t_k * head * (8 * jnp.dtype(dtype).itemsize + 8)
    assert pa.bwd_route(t_k, head, dtype, bq, bk) == plan
    # (a record that also propagates to the root logger is listed twice)
    lines = list({id(r): r.getMessage() for r in bwd_log.records
                  if "backward" in r.getMessage()}.values())
    assert len(lines) == 1 and f"takes the {want} route" in lines[0]
    assert f"{t_k} keys, head {head}" in lines[0]
    # a smaller chip: the same function, the other answer
    monkeypatch.setattr(pa, "_vmem_bytes", lambda: 16 << 20)
    assert pa.bwd_route(t_k, head, dtype, bq, bk).route == "split"



# ------------------------------------------------------------------ #
# a mask that is data (`keep`)


def _keep_case(t=128, heads=4, kv_heads=2, seed=11, share=0.3):
    """(q, k, v, keep): a random plane that keeps every query's own position
    and NO key of one whole (32 x 32) block below the diagonal."""
    r = np.random.RandomState(seed)
    draw = lambda h: jnp.asarray(r.randn(2, t, h, 16), jnp.float32)
    keep = r.rand(2, t, t) < share
    keep |= np.eye(t, dtype=bool)[None]
    keep[:, 64:96, 0:32] = False
    return draw(heads), draw(kv_heads), draw(kv_heads), jnp.asarray(keep)


def _dense_keep(q, k, v, keep):
    """(out, lse) by a dense mask: j <= i and keep[i, j]."""
    b, t, h, d = q.shape
    k, v = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    mask = jnp.tril(jnp.ones((t, t), bool))[None] & keep
    s = jnp.where(mask[:, None], s, -jnp.inf)
    return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v),
            jax.nn.logsumexp(s, axis=-1))


@pytest.mark.parametrize("route", ["resident", "split"])
@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (8, 2)])
def test_keep_forward_and_both_backward_routes_match_a_dense_mask(route, heads, kv_heads,
                                                                  monkeypatch):
    from elasticdl_tpu.ops.pallas_attention import flash_attention_lse

    take_route(monkeypatch, route)
    q, k, v, keep = _keep_case(heads=heads, kv_heads=kv_heads)
    probe = jnp.asarray(np.random.RandomState(5).randn(*q.shape), jnp.float32)

    def loss(f):
        def value(q, k, v):
            out, lse = f(q, k, v)
            return jnp.sum(probe * out) + jnp.sum(jnp.sin(lse)), (out, lse)
        return jax.value_and_grad(value, argnums=(0, 1, 2), has_aux=True)

    flash = lambda *a: flash_attention_lse(*a, keep=keep, block_q=32, block_k=32,
                                           interpret=True)
    ((_, got), got_grads), ((_, want), want_grads) = loss(flash)(q, k, v), loss(
        lambda *a: _dense_keep(*a, keep))(q, k, v)
    for a, b in zip(got + got_grads, want + want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("route", ["resident", "split"])
def test_keep_of_all_ones_is_the_causal_call_to_the_bit(route, monkeypatch):
    take_route(monkeypatch, route)
    q, k, v, _ = _keep_case(t=96)
    ones = jnp.ones((2, 96, 96), jnp.int8)
    f = lambda keep: jax.value_and_grad(lambda *a: jnp.sum(flash_attention(
        *a, keep=keep, block_q=32, block_k=32, interpret=True) ** 2), argnums=(0, 1, 2))
    for a, b in zip(jax.tree_util.tree_leaves(f(ones)(q, k, v)),
                    jax.tree_util.tree_leaves(f(None)(q, k, v))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("route,keep,want", [
    ("resident", False, {"flash_attention_fwd": (2, 4, 4, 4), "flash_attention_bwd": (2, 2, 8)}),
    ("split", False, {"flash_attention_fwd": (2, 4, 4, 4), "flash_attention_bwd_dq": (2, 4, 4, 4),
                      "flash_attention_bwd_dkv": (2, 2, 4, 8)}),
    ("resident", True, {"flash_attention_sel_fwd": (2, 4, 4, 4),
                        "flash_attention_sel_bwd": (2, 2, 8)}),
    ("split", True, {"flash_attention_sel_fwd": (2, 4, 4, 4),
                     "flash_attention_sel_bwd_dq": (2, 4, 4, 4),
                     "flash_attention_sel_bwd_dkv": (2, 2, 4, 8)}),
])
def test_keep_none_lowers_to_the_kernels_it_always_did(route, keep, want, monkeypatch):
    """Read off the lowered calls: without `keep` the names and grids of
    before, with it names of its own on the SAME grids (no block is skipped for
    being empty of kept keys), and one operand more."""
    take_route(monkeypatch, route)
    q, k, v, plane = _keep_case()
    f = lambda *a: jnp.sum(flash_attention(*a, keep=plane if keep else None, block_q=32,
                                           block_k=32, interpret=True) ** 2)
    jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v).jaxpr
    assert _kernel_grids(jaxpr) == want
    operands = []
    equations(jaxpr, lambda eqn: eqn.primitive.name == "pallas_call"
              and operands.append(len(eqn.invars)))
    # offsets, q, k, v (+ keep); offsets, q, k, v, out, do, lse (+ keep)
    assert sorted(set(operands)) == ([5, 8] if keep else [4, 7])


def test_a_keep_call_plans_smaller_q_blocks_and_counts_its_strip():
    from elasticdl_tpu.ops import pallas_attention as pa

    shape = (1, 16384, 32, 128)
    assert pa._plan_blocks(shape, shape, None, None, dtype=jnp.bfloat16) == (1024, 1024)
    assert pa._plan_blocks(shape, shape, None, None, dtype=jnp.bfloat16, keep=True) == (512, 1024)
    vmem = 128 << 20
    plan = lambda bq, keep: pa._bwd_plan(16384, 128, "bfloat16", bq, 1024, vmem, keep)
    assert plan(1024, False).route == "resident" and plan(512, True).route == "resident"
    assert plan(1024, True).route == "split"
    assert plan(512, True).vmem_bytes - plan(512, False).vmem_bytes \
        == 2 * 512 * 16384 + 4 * 512 * 1024
    # an int8 tile has 32 rows: a sequence with no such block is declined
    assert pa._plan_blocks((1, 48, 2, 16), (1, 48, 2, 16), None, None, keep=True) is None


def test_keep_takes_no_window_and_no_offsets(monkeypatch):
    q, k, v, keep = _keep_case(t=64)
    keep = keep[:, :64, :64]
    with pytest.raises(ValueError, match="without a window"):
        flash_attention(q, k, v, keep=keep, window=8, interpret=True)
    with pytest.raises(ValueError, match="without a window"):
        flash_attention(q, k, v, keep=keep, q_offset=64, interpret=True)
    with pytest.raises(ValueError, match="no head axis"):
        flash_attention(q, k, v, keep=keep[:, None], interpret=True)
    with pytest.raises(ValueError, match="int8 or bool"):
        flash_attention(q, k, v, keep=keep.astype(jnp.float32), interpret=True)
    monkeypatch.setenv("EDL_FLASH", "1")
    monkeypatch.setenv("EDL_FLASH_INTERPRET", "1")
    assert can_flash(q.shape, k.shape, keep=True)
    assert not can_flash(q.shape, k.shape, keep=True, window=8)
    assert not can_flash(q.shape, k.shape, keep=True, q_offset=64)
    assert not can_flash(q.shape, k.shape, keep=True, kv_offset=jnp.int32(0))
    # `full_attention` then takes its XLA path, with the same mask
    jaxpr = jax.make_jaxpr(lambda *a: full_attention(*a, keep=keep, window=8))(q, k, v).jaxpr
    assert not _kernel_grids(jaxpr)


def test_full_attention_passes_its_keep_to_the_kernel(monkeypatch):
    monkeypatch.setenv("EDL_FLASH", "1")
    monkeypatch.setenv("EDL_FLASH_INTERPRET", "1")
    monkeypatch.setattr("elasticdl_tpu.ops.pallas_attention.SEL_BLOCK_Q", 32)
    monkeypatch.setattr("elasticdl_tpu.ops.pallas_attention.DEFAULT_BLOCK_K", 32)
    q, k, v, keep = _keep_case()
    jaxpr = jax.make_jaxpr(lambda *a: full_attention(*a, keep=keep, with_lse=True))(q, k, v).jaxpr
    assert pallas_calls(jaxpr, "flash_attention_sel_fwd") == 1
    got, want = full_attention(q, k, v, keep=keep, with_lse=True), _dense_keep(q, k, v, keep)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)
