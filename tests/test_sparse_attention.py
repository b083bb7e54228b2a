"""Learned selection of keys (ops/sparse_attention.py: index scores, the exact
selection of a query's K best keys with its rule on ties, the indexer's KL
loss and its custom gradient) against their plain forms, at small sizes on
the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import sparse_attention as sa
from elasticdl_tpu.ops.attention import full_attention
from tests.conftest import equations

B, T, H, HKV, D, HI, DI = 2, 64, 4, 2, 16, 3, 8


def _draw(seed=0):
    r = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)
    return dict(q_index=f(B, T, HI, DI), k_index=f(B, T, DI), w=f(B, T, HI),
                q=f(B, T, H, D), k=f(B, T, HKV, D), v=f(B, T, HKV, D))


def _plain_scores(q_index, k_index, w):
    per_head = jax.nn.relu(jnp.einsum("bthd,bsd->bhts", q_index, k_index))
    return jnp.einsum("bhts,bth->bts", per_head, w)


def _top_k_mask(scores, k):
    """The reference's rule: `lax.top_k` over the masked prefix."""
    t = scores.shape[-1]
    causal = np.tril(np.ones((t, t), bool))
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(k, t))
    mask = np.zeros(scores.shape, bool)
    np.put_along_axis(mask, np.asarray(idx), True, axis=-1)
    return mask & causal


def _select_from(monkeypatch, scores, k):
    """`select` on a GIVEN score plane: the block of the plane it asks for is
    looked up by the row numbers handed to it as the index queries."""
    b, t, _ = scores.shape
    monkeypatch.setattr(sa, "_score_block", lambda q_rows, k_index, w_rows: scores[
        :, q_rows[0, :, 0, 0].astype(jnp.int32), :])
    rows = jnp.broadcast_to(jnp.arange(t, dtype=jnp.float32)[None, :, None, None],
                            (b, t, 1, 1))
    return sa.select(rows, jnp.zeros((b, t, 1)), jnp.zeros((b, t, 1)), k)


def _plain_kl(scores, q, k, keep, detach=True):
    kept = keep != 0
    s = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, H // HKV, axis=2)) * D ** -0.5
    p = jax.nn.softmax(jnp.where(kept[:, None], s, -jnp.inf), axis=-1)
    target = jnp.mean(p, axis=1)
    if detach:
        target = jax.lax.stop_gradient(target)
    log_pi = jnp.where(kept, jax.nn.log_softmax(
        jnp.where(kept, scores, -jnp.inf), axis=-1), 0.0)
    log_target = jnp.log(jnp.where(target > 0, target, 1.0))
    return jnp.sum(jnp.where(kept, target * (log_target - log_pi), 0.0)) / (T * B)


@pytest.mark.parametrize("rows", [256, 16, 1])
def test_index_scores_are_the_weighted_relu_of_every_head(rows, monkeypatch):
    monkeypatch.setattr(sa, "SCORE_ROWS", rows)
    d = _draw()
    np.testing.assert_allclose(sa.index_scores(d["q_index"], d["k_index"], d["w"]),
                               _plain_scores(d["q_index"], d["k_index"], d["w"]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k", [1, 7, 16, 63, 64, 200])
@pytest.mark.parametrize("rows", [256, 16])
def test_select_is_top_k_of_the_causal_prefix(k, rows, monkeypatch):
    monkeypatch.setattr(sa, "SCORE_ROWS", rows)
    scores = jnp.asarray(np.random.default_rng(k).normal(size=(B, T, T)), jnp.float32)
    threshold, keep, counts = _select_from(monkeypatch, scores, k)
    keep = np.asarray(keep)
    assert keep.dtype == np.int8
    np.testing.assert_array_equal(keep != 0, _top_k_mask(scores, k))
    # rows with at most k keys keep their whole prefix, the others exactly k
    np.testing.assert_array_equal(keep.sum(-1), np.broadcast_to(
        np.minimum(np.arange(T) + 1, k), (B, T)))
    # the threshold is the least kept score
    np.testing.assert_array_equal(
        np.asarray(threshold), np.where(keep != 0, np.asarray(scores), np.inf).min(-1))
    assert int(counts["tie_rows"]) == 0
    assert int(counts["selected_pairs"]) == keep.sum()
    assert int(counts["causal_pairs"]) == B * T * (T + 1) // 2


def test_select_ranks_the_scores_of_its_operands():
    d = _draw(4)
    operands = (d["q_index"], d["k_index"], d["w"])
    _, keep, _ = sa.select(*operands, 12)
    np.testing.assert_array_equal(np.asarray(keep) != 0,
                                  _top_k_mask(sa.index_scores(*operands), 12))


def test_ties_go_to_the_lower_key_index_and_are_counted(monkeypatch):
    """Forced ties: a row of equal scores, a row whose k-th largest is shared
    by thirty keys, a row of zeros of both signs."""
    scores = np.random.default_rng(0).normal(size=(B, T, T)).astype(np.float32)
    scores[:, 50, :] = 0.25
    scores[:, 40, :30] = 1.5
    scores[0, 60, ::2], scores[0, 60, 1::2] = 0.0, -0.0
    _, keep, counts = _select_from(monkeypatch, jnp.asarray(scores), 16)
    keep = np.asarray(keep) != 0
    np.testing.assert_array_equal(keep, _top_k_mask(jnp.asarray(scores), 16))
    np.testing.assert_array_equal(np.flatnonzero(keep[0, 50]), np.arange(16))
    assert keep.sum(-1)[:, 16:].min() == keep.sum(-1)[:, 16:].max() == 16
    assert int(counts["tie_rows"]) == 2 * B + 1


def test_live_blocks_count_the_blocks_that_hold_a_kept_key(monkeypatch):
    monkeypatch.setattr(sa, "LIVE_BLOCK", 16)
    monkeypatch.setattr(sa, "SCORE_ROWS", 8)    # two row blocks a square block
    scores = np.full((1, T, T), -1.0, np.float32)
    scores[:, :, :4] = 1.0                      # every late query keeps keys 0-3
    _, keep, counts = _select_from(monkeypatch, jnp.asarray(scores), 4)
    # rows 0-15 live in block (0, 0); later row blocks only in column block 0
    assert int(counts["live_blocks"]) == 4
    assert int(counts["causal_blocks"]) == 4 * 5 // 2


def _kl_case(seed=1, k=16):
    d = _draw(seed)
    _, keep, _ = sa.select(d["q_index"], d["k_index"], d["w"], k)
    _, lse = full_attention(d["q"], d["k"], d["v"], keep=keep, with_lse=True)
    return d, sa.index_scores(d["q_index"], d["k_index"], d["w"]), keep, lse


@pytest.mark.parametrize("rows", [128, 8])
def test_index_kl_and_its_gradient_are_the_plain_form_s(rows, monkeypatch):
    monkeypatch.setattr(sa, "KL_ROWS", rows)
    d, scores, keep, lse = _kl_case()

    def ours(q_index, k_index, w):
        return sa.index_kl(q_index, k_index, w, d["q"], d["k"], lse, keep)

    def plain(q_index, k_index, w):
        return _plain_kl(_plain_scores(q_index, k_index, w), d["q"], d["k"], keep)

    operands = (d["q_index"], d["k_index"], d["w"])
    got, got_grads = jax.value_and_grad(ours, argnums=(0, 1, 2))(*operands)
    want, want_grads = jax.value_and_grad(plain, argnums=(0, 1, 2))(*operands)
    assert float(want) > 0.05
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_grads, want_grads):
        assert float(jnp.abs(b).max()) > 1e-5
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-7)


def test_index_kl_gives_the_attention_no_gradient():
    """p̂ is a target: q, k and the logsumexp receive NOTHING from the loss,
    where the plain form without the stop_gradient gives them plenty."""
    d, scores, keep, lse = _kl_case()
    grads = jax.grad(lambda q, k, lse: sa.index_kl(
        d["q_index"], d["k_index"], d["w"], q, k, lse, keep),
        argnums=(0, 1, 2))(d["q"], d["k"], lse)
    for g in grads:
        assert float(jnp.abs(g).max()) == 0.0
    leaky = jax.grad(lambda q, k: _plain_kl(scores, q, k, keep, detach=False),
                     argnums=(0, 1))(d["q"], d["k"])
    assert all(float(jnp.abs(g).max()) > 1e-6 for g in leaky)


def test_the_selection_is_not_differentiated_and_is_named():
    d = _draw()
    operands = (d["q_index"], d["k_index"], d["w"])
    grads = jax.grad(lambda w: jnp.sum(sa.select(d["q_index"], d["k_index"], w, 8)[0]))(d["w"])
    assert float(jnp.abs(grads).max()) == 0.0
    names = []
    jaxpr = jax.make_jaxpr(lambda *a: sa.select(*a, 8)[:2])(*operands).jaxpr
    equations(jaxpr, lambda eqn: eqn.primitive.name == "name"
              and names.append(eqn.params["name"]))
    assert tuple(names) == sa.SELECTION_NAMES


def test_a_bit_pattern_orders_as_its_float():
    values = np.array([-np.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, np.inf], np.float32)
    keys = np.asarray(sa._ordered(jnp.asarray(values)))
    assert np.all(np.diff(keys.astype(np.int64)) > 0)
    np.testing.assert_array_equal(np.asarray(sa._unordered(jnp.asarray(keys))), values)
