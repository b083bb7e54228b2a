"""The grouped matmul kernel (`ops/pallas_gmm.py`) against `jax.lax.ragged_dot`,
in interpret mode on the CPU at small shapes: forward, dx and dW; the
metadata of its visits against a brute-force count; the experts' bodies
through `ops/moe.py::_expert_body` on the kernel's route."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import moe as moe_ops
from elasticdl_tpu.ops import pallas_gmm
from elasticdl_tpu.ops.pallas_attention import interpret_mode

# name: (rows M, K, N, group sizes, row tile, column tile or None for the rule's)
CASES = {
    "boundaries_inside_a_row_tile": (64, 32, 256, (10, 23, 20, 11), 16, 128),
    "boundaries_on_row_tiles": (64, 32, 128, (16, 32, 0, 16), 16, 128),
    "empty_groups_first_middle_last": (70, 48, 128, (0, 33, 0, 30, 0), 16, 128),
    "widths_no_tile_divides": (96, 336, 232, (40, 7, 0, 49), 32, 128),
    "widths_no_tile_divides_whole_n": (96, 336, 232, (40, 7, 0, 49), 32, None),
    "rows_past_the_last_group": (128, 32, 128, (9, 0, 30), 16, 128),
    "no_group_has_a_row": (64, 32, 128, (0, 0, 0), 16, 128),
    "rows_no_tile_divides": (50, 32, 128, (20, 30), 16, 128),
    "one_tile_many_groups": (32, 16, 128, (3, 5, 0, 7, 2, 9), 32, 128),
}


def operands(name, dtype=jnp.float32):
    m, k, n, sizes, tm, tn = CASES[name]
    r = np.random.default_rng(sorted(CASES).index(name))
    lhs = jnp.asarray(r.normal(size=(m, k)), dtype)
    rhs = jnp.asarray(r.normal(size=(len(sizes), k, n)) * 0.3, dtype)
    dy = jnp.asarray(r.normal(size=(m, n)), dtype)
    return lhs, rhs, dy, jnp.asarray(sizes, jnp.int32), tm, tn


def ragged_dot_zero_tail(lhs, rhs, sizes):
    """`ragged_dot` with the rows past the last group in a group of their own
    whose matrix is zero: defined everywhere, zeros there."""
    tail = lhs.shape[0] - jnp.sum(sizes)
    return jax.lax.ragged_dot(
        lhs, jnp.concatenate([rhs, jnp.zeros_like(rhs[:1])]),
        jnp.concatenate([sizes, tail[None]]))


@pytest.mark.parametrize("name", sorted(CASES))
def test_forward_matches_ragged_dot_and_rows_past_the_groups_are_zero(name):
    lhs, rhs, _, sizes, tm, tn = operands(name)
    with interpret_mode():
        got = pallas_gmm._gmm(lhs, rhs, sizes, tm=tm, tn=tn, interpret=True)
    np.testing.assert_allclose(got, ragged_dot_zero_tail(lhs, rhs, sizes),
                               rtol=1e-5, atol=1e-5)
    assert not np.any(np.asarray(got)[int(jnp.sum(sizes)):])


@pytest.mark.parametrize("name", sorted(CASES))
def test_dx_matches_ragged_dot_and_rows_past_the_groups_take_no_gradient(name):
    lhs, rhs, dy, sizes, tm, tn = operands(name)
    want = jax.vjp(lambda a: ragged_dot_zero_tail(a, rhs, sizes), lhs)[1](dy)[0]
    with interpret_mode():
        got = pallas_gmm._gmm(dy, rhs, sizes, transpose_rhs=True, tm=tm, tn=tn,
                              interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not np.any(np.asarray(got)[int(jnp.sum(sizes)):])


@pytest.mark.parametrize("name", sorted(CASES))
def test_dw_matches_ragged_dot_and_rows_past_the_groups_add_nothing(name):
    lhs, rhs, dy, sizes, tm, tn = operands(name)
    want = jax.vjp(lambda b: ragged_dot_zero_tail(lhs, b, sizes), rhs)[1](dy)[0]
    total = int(jnp.sum(sizes))
    # what lies past the last group must not matter, whatever it is
    poison = jnp.where(jnp.arange(lhs.shape[0])[:, None] < total, 1.0, jnp.nan)
    with interpret_mode():
        got = pallas_gmm._tgmm(lhs * poison, dy * poison, sizes, tm=tm, tn=tn,
                               interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-5)
    for g, size in enumerate(np.asarray(sizes)):
        if size == 0:
            assert not np.any(np.asarray(got[g])), g


@pytest.mark.parametrize("name", ["boundaries_inside_a_row_tile",
                                  "empty_groups_first_middle_last",
                                  "widths_no_tile_divides_whole_n",
                                  "rows_past_the_last_group"])
def test_custom_vjp_is_ragged_dots_at_the_rules_own_tiles(name):
    """`grouped_matmul` as the experts call it — tiles from `tiles`, the
    backward through the `custom_vjp` — in bfloat16 operands: the same
    products, float32 accumulation, the result cast to bfloat16."""
    lhs, rhs, dy, sizes, _, _ = operands(name, jnp.bfloat16)
    want, vjp = jax.vjp(lambda a, b: ragged_dot_zero_tail(a, b, sizes), lhs, rhs)
    with interpret_mode():
        got, got_vjp = jax.vjp(lambda a, b: pallas_gmm.grouped_matmul(a, b, sizes), lhs, rhs)
        got_dx, got_dw = got_vjp(dy)
    assert got.dtype == got_dx.dtype == got_dw.dtype == jnp.bfloat16
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    for g, w in zip((got, got_dx, got_dw), (want, *vjp(dy))):
        np.testing.assert_allclose(f32(g), f32(w), rtol=2e-2, atol=2e-2)


def brute_force_visits(sizes, m, tm, visit_empty):
    """(group, row tile, first row, end row) of every visit, by walking rows."""
    ends = np.cumsum(sizes)
    visits = []
    for g, (size, end) in enumerate(zip(sizes, ends)):
        rows = range(end - size, end)
        if size == 0 and visit_empty:
            visits.append((g, min((end - size) // tm, -(-m // tm) - 1), 0, 0))
        for tile in sorted({r // tm for r in rows}):
            visits.append((g, tile, end - size, end))
    return visits


@pytest.mark.parametrize("visit_empty", [False, True])
@pytest.mark.parametrize("name", sorted(CASES))
def test_visits_are_the_brute_force_count(name, visit_empty):
    m, _, _, sizes, tm, _ = CASES[name]
    v = pallas_gmm.row_tile_visits(jnp.asarray(sizes, jnp.int32), m, tm, visit_empty)
    want = brute_force_visits(sizes, m, tm, visit_empty)
    count = int(v.count)
    steps = -(-m // tm) + len(sizes) - 1
    assert count == len(want) <= steps == v.group.shape[0]
    got = list(zip(*(np.asarray(a)[:count].tolist() for a in (v.group, v.tile, v.lo, v.hi))))
    assert [(g, t) for g, t, _, _ in got] == [(g, t) for g, t, _, _ in want]
    assert [(lo, hi) for g, t, lo, hi in got if hi > lo] == \
        [(lo, hi) for g, t, lo, hi in want if hi > lo]
    with_rows = len({r // tm for r in range(sum(sizes))})
    assert int(v.row_tiles) == with_rows == pallas_gmm.row_tiles(sum(sizes), tm)
    # past the visits: no rows, and every row tile without a row exactly once
    tail_tiles = np.asarray(v.tile)[count:]
    assert not np.any(np.asarray(v.hi)[count:])
    if not visit_empty:
        assert set(range(with_rows, -(-m // tm))) <= set(tail_tiles.tolist())
        assert np.all(np.diff(np.asarray(v.tile)) >= 0)
    # a step past the visits reads what the last visit read: no new block
    assert len(set(np.asarray(v.lhs_tile)[max(count - 1, 0):].tolist())) == 1
    assert len(set(np.asarray(v.group)[max(count - 1, 0):].tolist())) == 1


@pytest.mark.parametrize("m,k,n", [(6144, 2688, 1856), (6144, 1856, 2688),
                                   (65536, 2048, 1024), (65536, 1024, 2048),
                                   (64, 32, 128), (4096, 16384, 16384)])
def test_tiles_come_from_the_shapes_and_fit_vmem(m, k, n):
    vmem = pallas_gmm._vmem_bytes()
    for transposed in (False, True):
        t = pallas_gmm.tiles(m, k, n, jnp.bfloat16, transposed)
        assert t.rows % 16 == 0 and t.rows <= max(256, -(-m // 16) * 16)
        assert t.cols == n or (t.cols % 128 == 0 and 0 < t.cols < n)
        assert t.vmem_limit < vmem
        blocks = 2 * 2 * (t.rows * k + k * t.cols + t.rows * t.cols)
        assert blocks <= vmem // 2
    if k * n * 4 <= vmem // 8:
        assert pallas_gmm.tiles(m, k, n, jnp.bfloat16).cols == n   # one column tile


@pytest.mark.parametrize("body", ["gated_silu", "relu2"])
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_expert_body_on_the_kernels_route_is_the_ragged_dot_routes(body, direction):
    """`_expert_body` inside interpret mode takes the kernel, outside it
    `ragged_dot`: the same unit, rows past the last group zeros and without
    gradient."""
    r = np.random.default_rng(3)
    m, c, f, sizes = 64, 32, 128, jnp.asarray([10, 0, 23, 11], jnp.int32)
    total = int(jnp.sum(sizes))
    xs = jnp.asarray(r.normal(size=(m, c)), jnp.float32)
    shapes = [(4, c, f)] * (2 if body == "gated_silu" else 1) + [(4, f, c)]
    experts = tuple(jnp.asarray(r.normal(size=s) * 0.3, jnp.float32) for s in shapes)
    lent = sizes.at[-1].add(m - total)                       # every row defined
    live = (jnp.arange(m) < total)[:, None]
    ref = lambda xs, *w: jnp.where(live, moe_ops._expert_body(xs, w, lent, jnp.float32), 0.0)
    run = lambda xs, *w: moe_ops._expert_body(xs, w, sizes, jnp.float32)
    if direction == "forward":
        with interpret_mode():
            assert pallas_gmm.runnable()
            got = run(xs, *experts)
        assert not pallas_gmm.runnable()
        np.testing.assert_allclose(got, ref(xs, *experts), rtol=1e-4, atol=1e-5)
        assert not np.any(np.asarray(got)[total:])
        return
    probe = jnp.asarray(r.normal(size=(m, c)), jnp.float32)
    args = tuple(range(1 + len(experts)))
    with interpret_mode():
        got = jax.grad(lambda *a: jnp.sum(probe * run(*a)), argnums=args)(xs, *experts)
    want = jax.grad(lambda *a: jnp.sum(probe * ref(*a)), argnums=args)(xs, *experts)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)
    assert not np.any(np.asarray(got[0])[total:])
    assert not np.any(np.asarray(got[1])[1])                 # the empty group's matrices


def test_a_first_pass_with_no_held_pair_skips_every_tile_and_adds_nothing():
    """The held dispatch runs its first pass whatever the routing: with no
    pair on a held expert, on the kernels' route, every row is past the last
    group — the value and every gradient are exact zeros."""
    n, c, f, e, k, held = 64, 32, 128, 16, 3, (4, 4)
    r = np.random.default_rng(6)
    x = jnp.asarray(r.normal(size=(n, c)), jnp.float32)
    experts = tuple(jnp.asarray(r.normal(size=s) * 0.3, jnp.float32)
                    for s in ((4, c, f), (4, c, f), (4, f, c)))
    weights = jnp.asarray(r.uniform(0.1, 1.0, size=(n, k)), jnp.float32)
    idx = jnp.asarray(r.integers(8, e, size=(n, k)), jnp.int32)    # experts 8-15 only
    probe = jnp.asarray(r.normal(size=(n, c)), jnp.float32)
    run = lambda x, weights, *w: jnp.sum(probe * moe_ops.dropless_moe(
        x, idx, weights, w, held=held, num_experts=e, compute_dtype=jnp.float32))
    with interpret_mode():
        value, grads = jax.value_and_grad(run, argnums=(0, 1, 2, 3, 4))(x, weights, *experts)
    assert float(value) == 0.0 and not any(np.any(np.asarray(g)) for g in grads)


@pytest.mark.parametrize("pass_rows", [0, 40])
def test_held_row_tiles_is_the_kernels_own_count(pass_rows, monkeypatch):
    """`ops.moe.held_row_tiles` — what `router_state/held_row_tiles` adds up —
    against the `row_tiles` of the visits the kernel's metadata makes for
    each pass's group sizes, and against a count of rows."""
    n, k, e, held = 64, 3, 16, (4, 4)
    r = np.random.default_rng(5)
    idx = jnp.asarray(r.integers(0, e, size=(n, k)), jnp.int32)
    if pass_rows:
        monkeypatch.setattr(moe_ops, "held_pass_rows", lambda pairs, e, count: pass_rows)
    rows = moe_ops.held_pass_rows(n * k, e, held[1])
    tm = pallas_gmm.row_tile(rows)
    sizes = np.bincount(np.asarray(idx).ravel(), minlength=e)[4:8]
    got = int(moe_ops.held_row_tiles(jnp.int32(sizes.sum()), n * k, e, held[1]))
    ends = np.cumsum(sizes)
    want = by_rows = 0
    for lo in range(0, n * k, rows):
        in_pass = np.clip(ends, lo, lo + rows) - np.clip(ends - sizes, lo, lo + rows)
        want += int(pallas_gmm.row_tile_visits(jnp.asarray(in_pass, jnp.int32), rows, tm).row_tiles)
        by_rows += -(-int(in_pass.sum()) // tm)
    assert got == want == by_rows
    assert got <= -(-int(sizes.sum()) // rows) * -(-rows // tm)
