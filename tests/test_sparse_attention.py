"""Learned selection of keys (ops/sparse_attention.py: index scores, the exact
selection of a query's K best keys with its rule on ties, the indexer's KL
loss and its custom gradient) against their plain forms, at small sizes on
the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import pallas_attention
from elasticdl_tpu.ops import sparse_attention as sa
from elasticdl_tpu.ops.attention import full_attention
from tests.conftest import equations, matmuls, pallas_calls, scans_with

B, T, H, HKV, D, HI, DI = 2, 64, 4, 2, 16, 3, 8


def _draw(seed=0, t=T):
    r = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)
    return dict(q_index=f(B, t, HI, DI), k_index=f(B, t, DI), w=f(B, t, HI),
                q=f(B, t, H, D), k=f(B, t, HKV, D), v=f(B, t, HKV, D))


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
    return jnp.sum(jnp.where(kept, target * (log_target - log_pi), 0.0)) / (keep.shape[1] * B)


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


def _kl_case(seed=1, k=16, t=T):
    d = _draw(seed, t)
    _, keep, _ = sa.select(d["q_index"], d["k_index"], d["w"], k)
    _, lse = full_attention(d["q"], d["k"], d["v"], keep=keep, with_lse=True)
    return d, sa.index_scores(d["q_index"], d["k_index"], d["w"]), keep, lse


@pytest.fixture
def kernel_route(monkeypatch):
    """The Pallas kernels' route on the CPU: the signal alone, so that they
    run by `pallas_call(interpret=True)`; key tiles of 128, so that a plane of
    256 keys has two."""
    monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
    monkeypatch.setattr(sa, "PULLBACK_KEYS", 128)


def _pulled_back(jaxpr):
    return pallas_calls(jaxpr, "index_score_bwd")


# the pull-back of a score block by `jax.vjp(_score_block)` (64 keys have no
# key tile) and by the kernel, two tiles a block of rows; the loss as it is
# (the rule's cotangent 1) and scaled (a cotangent that is no power of two:
# the rule's gradients are made before it is known)
@pytest.mark.parametrize("scale", [1.0, 0.37])
@pytest.mark.parametrize("route, t, rows", [
    ("vjp", 64, 128), ("vjp", 64, 8), ("kernel", 256, 128), ("kernel", 256, 32)])
def test_index_kl_and_its_gradient_are_the_plain_form_s(route, t, rows, scale, monkeypatch,
                                                        request):
    if route == "kernel":
        request.getfixturevalue("kernel_route")
    monkeypatch.setattr(sa, "KL_ROWS", rows)
    d, scores, keep, lse = _kl_case(t=t, k=16 * t // 64)

    def ours(q_index, k_index, w):
        return scale * sa.index_kl(q_index, k_index, w, d["q"], d["k"], lse, keep)

    def plain(q_index, k_index, w):
        return scale * _plain_kl(_plain_scores(q_index, k_index, w), d["q"], d["k"], keep)

    operands = (d["q_index"], d["k_index"], d["w"])
    assert _pulled_back(jax.make_jaxpr(jax.grad(ours))(*operands).jaxpr) == (route == "kernel")
    got, got_grads = jax.value_and_grad(ours, argnums=(0, 1, 2))(*operands)
    want, want_grads = jax.value_and_grad(plain, argnums=(0, 1, 2))(*operands)
    assert float(want) > 0.05 * scale
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_grads, want_grads):
        assert float(jnp.abs(b).max()) > 1e-5
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-7)


def _pull_back_case(rows, t, heads, first_row, dtype, draw):
    """A block of `rows` query rows from `first_row` on against t keys, and a
    cotangent that is zero on the keys of their future, as dL/dI is."""
    r = np.random.default_rng(rows + t + heads + first_row)
    q_rows, k_index = (jnp.asarray(draw(r, shape), dtype)
                       for shape in ((B, rows, heads, 64), (B, t, 64)))
    w_rows = jnp.asarray(r.normal(size=(B, rows, heads)), jnp.float32)
    causal = np.arange(t)[None, :] <= first_row + np.arange(rows)[:, None]
    d_scores = jnp.asarray(r.normal(size=(B, rows, t)) * causal, jnp.float32)
    return q_rows, k_index, w_rows, d_scores


def _both_pull_backs(q_rows, k_index, w_rows, d_scores, first_row, block_k):
    want = jax.vjp(sa._score_block, q_rows, k_index, w_rows)[1](d_scores)
    dq, dk, dw = sa.index_score_bwd(q_rows, k_index, w_rows, d_scores, jnp.int32(first_row),
                                    block_k=block_k, interpret=True)
    assert (dq.dtype, dk.dtype, dw.dtype) == (q_rows.dtype, jnp.float32, jnp.float32)
    # the kernel's layouts: heads before rows, dk's keys along the lanes
    return (jnp.moveaxis(dq, 1, 2), jnp.swapaxes(dk, 1, 2), jnp.moveaxis(dw, 1, 2)), want


_normal = lambda r, shape: r.normal(size=shape)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("which", ["first", "middle", "last"])
@pytest.mark.parametrize("heads", [16, 4])
@pytest.mark.parametrize("t", [512, 2048])
def test_the_pull_back_kernel_is_the_vjp_of_a_score_block(t, heads, which, dtype):
    """dq, dk and dw of `index_score_bwd` (interpret mode) against
    `jax.vjp(_score_block)`, four key tiles a block: the first block of rows
    skips three of them, the last none, and nothing changes. With bfloat16
    operands the kernel rounds dL/dI · w · mask to bfloat16 where the CPU's vjp
    keeps float32 (the chip rounds both): a bfloat16's width apart."""
    rows, first_row = 128, {"first": 0, "middle": t // 2, "last": t - 128}[which]
    operands = _pull_back_case(rows, t, heads, first_row, jnp.dtype(dtype), _normal)
    got, want = _both_pull_backs(*operands, first_row, t // 4)
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(b).max() > 1.0
        if dtype == "float32":
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-4)
        else:
            assert np.abs(a - b).max() <= 0.01 * np.abs(b).max()


def test_the_pull_back_kernel_gives_a_score_of_exactly_zero_no_gradient():
    """The relu's edge: operands of −1, 0 and 1 make a fifth of the scores
    exactly 0, where `jax.nn.relu` passes nothing back; sums of small integers
    are exact, so the two forms agree to the bit in dq and dk."""
    small = lambda r, shape: r.integers(-1, 2, size=shape) * (r.random(shape) < 0.05)
    q_rows, k_index, w_rows, d_scores = _pull_back_case(128, 512, 4, 384, jnp.float32, small)
    w_rows, d_scores = jnp.round(4 * w_rows), jnp.round(4 * d_scores)
    scores = jnp.einsum("brhd,bsd->bhrs", q_rows, k_index)
    assert 0.05 < float(jnp.mean(scores == 0)) < 0.99 and float(jnp.mean(scores > 0)) > 0.005
    got, want = _both_pull_backs(q_rows, k_index, w_rows, d_scores, 384, 128)
    for a, b in zip(got, want):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _planes(jaxpr, shape):
    """Equations of a jaxpr (kernels' bodies not) with a result of `shape`."""
    return equations(jaxpr, lambda eqn: any(
        getattr(v.aval, "shape", None) == shape for v in eqn.outvars))


def test_the_gradient_s_program_holds_the_kernel_or_the_vjp(monkeypatch, request):
    """The gradient's program evaluates a block ONCE: one scan, with one
    matmul of the heads' scores (B, Hi, R, T) and one of the target's (B, Hkv,
    G, R, T) — the forward rule's; the backward rule holds none. On the
    kernel's route that scan holds ONE `index_score_bwd` and, of arrays the
    size of the heads' scores, only those of the loss's own forward pass — no
    cotangent of them, no mask; on a plain CPU it holds no kernel and is the
    program it is with the kernels switched off, the heads' scores written and
    read."""
    monkeypatch.setattr(sa, "KL_ROWS", 128)
    d, _, keep, lse = _kl_case(t=256, k=64)
    operands = (d["q_index"], d["k_index"], d["w"])
    loss = lambda *a: sa.index_kl(*a, d["q"], d["k"], lse, keep)
    heads_scores, heads_target = (B, HI, 128, 256), (B, HKV, H // HKV, 128, 256)
    primal = jax.make_jaxpr(loss)(*operands).jaxpr
    forward = _planes(primal, heads_scores)
    assert forward >= 1
    assert (matmuls(primal, heads_scores), matmuls(primal, heads_target)) == (1, 1)

    def one_evaluation(jaxpr):
        scores = scans_with(jaxpr, lambda body: matmuls(body, heads_scores))
        return (len(scores) == 1 and matmuls(jaxpr, heads_scores) == 1
                and matmuls(jaxpr, heads_target) == 1
                and matmuls(scores[0], heads_target) == 1)

    plain = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*operands)
    assert _pulled_back(plain.jaxpr) == 0
    assert one_evaluation(plain.jaxpr)
    assert _planes(plain.jaxpr, heads_scores) > forward     # the vjp's cotangent and mask

    request.getfixturevalue("kernel_route")
    assert sa.pullback_keys(128, 256, DI, jnp.float32, jnp.float32) == 128
    kernel = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*operands).jaxpr
    assert _pulled_back(kernel) == 1 and one_evaluation(kernel)
    assert len(scans_with(kernel, lambda body: _pulled_back(body) == 1
                           and matmuls(body, heads_scores) == 1)) == 1
    assert _planes(kernel, heads_scores) == forward

    monkeypatch.setenv("EDL_FLASH", "0")
    assert sa.pullback_keys(128, 256, DI, jnp.float32, jnp.float32) is None
    assert str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*operands)) == str(plain)


def _names_in(jaxpr):
    names = []
    equations(jaxpr, lambda eqn: eqn.primitive.name == "name" and names.append(eqn.params["name"]))
    return tuple(names)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_forward_rule_keeps_its_three_gradients_and_no_operand(dtype):
    """What the rule hands its backward: the gradients for q_index, k_index
    and w in the operands' own shapes and dtypes, under
    `INDEX_GRADIENT_NAMES` — and none of q, k, lse or keep."""
    d, _, keep, lse = _kl_case()
    operands = [d["q_index"].astype(dtype), d["k_index"].astype(dtype), d["w"]]
    rest = (d["q"].astype(dtype), d["k"].astype(dtype), lse, keep)
    loss, kept = jax.eval_shape(sa._index_kl_fwd, *operands, *rest)
    assert (loss.shape, loss.dtype) == ((), jnp.float32)
    assert [(x.shape, x.dtype) for x in kept] == [(x.shape, x.dtype) for x in operands]
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: sa.index_kl(*a, *rest), argnums=(0, 1, 2)))(
        *operands).jaxpr
    assert _names_in(jaxpr) == sa.INDEX_GRADIENT_NAMES
    assert not set(sa.INDEX_GRADIENT_NAMES) & set(sa.SELECTION_NAMES)


def test_a_recomputation_that_keeps_the_gradients_evaluates_nothing_again(monkeypatch):
    """`jax.checkpoint` around the loss: under `KEEP_SELECTION` the gradient's
    program holds the forward rule's one scan; under a policy that knows none
    of the rule's names the recomputation holds it again — and the gradients
    are the same, to the bit where recomputing is exact."""
    monkeypatch.setattr(sa, "KL_ROWS", 32)
    d, _, keep, lse = _kl_case()
    operands = (d["q_index"], d["k_index"], d["w"])
    heads_scores = (B, HI, 32, T)

    def gradient(policy):
        loss = lambda *a: 0.37 * sa.index_kl(*a, d["q"], d["k"], lse, keep)  # a new closure
        return jax.grad(jax.checkpoint(loss, policy=policy), argnums=(0, 1, 2))

    count = lambda policy: matmuls(
        jax.make_jaxpr(gradient(policy))(*operands).jaxpr, heads_scores)
    assert count(sa.KEEP_SELECTION) == 1
    assert count(pallas_attention.KEEP_RESIDUALS) == 2
    for a, b in zip(gradient(sa.KEEP_SELECTION)(*operands),
                    gradient(pallas_attention.KEEP_RESIDUALS)(*operands)):
        assert float(jnp.abs(a).max()) > 1e-6
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("rows, t, q_dtype, k_dtype, keys", [
    (128, 16384, "bfloat16", "bfloat16", 1024),      # the cell's
    (128, 512, "float32", "float32", 512),
    (128, 40, "float32", "float32", None),           # the tiny preset: no key tile
    (128, 1024 + 64, "float32", "float32", None),
    (8, 512, "bfloat16", "bfloat16", None),          # half a bfloat16 tile of rows
    (8, 512, "float32", "float32", 512),
    (128, 512, "float32", "bfloat16", None),
])
def test_the_kernel_takes_the_shapes_it_has_tiles_for(rows, t, q_dtype, k_dtype, keys,
                                                       monkeypatch):
    assert sa.pullback_keys(rows, t, 64, q_dtype, k_dtype) is None     # a plain CPU
    monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
    assert sa.pullback_keys(rows, t, 64, q_dtype, k_dtype) == keys


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
    jaxpr = jax.make_jaxpr(lambda *a: sa.select(*a, 8)[:2])(*operands).jaxpr
    assert _names_in(jaxpr) == sa.SELECTION_NAMES


def test_a_bit_pattern_orders_as_its_float():
    values = np.array([-np.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, np.inf], np.float32)
    keys = np.asarray(sa._ordered(jnp.asarray(values)))
    assert np.all(np.diff(keys.astype(np.int64)) > 0)
    np.testing.assert_array_equal(np.asarray(sa._unordered(jnp.asarray(keys))), values)
