"""Sharded embedding engine: manual shard_map path vs auto (GSPMD) path vs a
dense reference, forward and backward, on 1-D and 2-D meshes.

Mirrors the reference's embedding tests (reference:
elasticdl/python/tests/embedding_table_test.py, embedding_layer_test.py) —
row lookup, padding ids, combiners, sparse-gradient correctness — but the
"PS shard" here is a mesh row-shard.
"""

import contextlib
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticdl_tpu.ops import embedding as emb_ops
from elasticdl_tpu.api.layers import Embedding


def make_table(mesh, V=512, D=16, seed=0):
    rng = np.random.RandomState(seed)
    table = rng.randn(V, D).astype(np.float32)
    sharded = jax.device_put(
        table, NamedSharding(mesh, P(tuple(mesh.axis_names), None))
    )
    return table, sharded


@contextlib.contextmanager
def _route(monkeypatch, route, n, rows):
    """Shrink the gates so that small shapes take `route` of the
    embedding backward ("kernel" runs the real Mosaic kernel in interpret
    mode), and check that `n` ids into `rows` rows do take it."""
    from elasticdl_tpu.ops import pallas_scatter as ps
    from elasticdl_tpu.ops.pallas_attention import interpret_mode

    if route != "flat":
        monkeypatch.setattr(emb_ops, "SORTED_MIN_IDS", 1)
    if route == "kernel":
        monkeypatch.setattr(ps, "BLOCK_ROWS", 256)
    elif route == "tiled":
        monkeypatch.setattr(emb_ops, "TILE_ROWS", 16)
    with interpret_mode() if route == "kernel" else contextlib.nullcontext():
        assert emb_ops.backward_route(n, rows, ps.runnable()) == route
        yield


@pytest.mark.parametrize("n,rows,runnable,want", [
    # the benchmark's three streams, on the chip and off it
    (8192 * 26, 33_800_192, True, "kernel"),     # deepfm-criteo
    (8192 * 26, 33_800_192, False, "tiled"),
    (32768 * 26, 23_472_128, True, "kernel"),    # deepfm-criteo1tb, a shard
    (32768 * 26, 23_472_128, False, "tiled"),
    (55296 * 26, 2_605_056, True, "kernel"),     # xdeepfm-criteo
    (55296 * 26, 2_605_056, False, "tiled"),
    # one step outside each gate
    (4095, 33_800_192, True, "flat"),            # too few ids to sort
    (8192 * 26, 2 * 2048 - 1, True, "flat"),     # under two blocks of rows
    (1_846_154, 300_000, True, "tiled"),         # window estimate 16384.002
])
def test_backward_route(n, rows, runnable, want):
    """The route is a pure function of the stream's length, the table's
    rows and whether the kernel can run: every benchmark cell takes the
    kernel on the chip and the tiled scan off it, and each gate turns a
    shape away one step past its value."""
    from elasticdl_tpu.ops import pallas_scatter as ps

    # the tables' rows above are the configurations' own, padded
    assert (emb_ops.padded_vocab(1_300_000 * 26),
            emb_ops.padded_vocab(3_611_000 * 26) // 4,
            ps.BLOCK_ROWS) == (33_800_192, 23_472_128, 2048)
    assert emb_ops.backward_route(n, rows, runnable) == want


def test_gather_rows_backward_matches_take_vjp():
    """gather_rows' backward by its default route must equal the plain
    take VJP — including duplicate ids (accumulation) and a bf16 table,
    whose gradient round-trips through the f32 accumulator."""
    t = jnp.asarray(np.random.RandomState(0).randn(128, 16), jnp.float32)
    ids = jnp.asarray([[3, 3, 7], [0, 127, 3]], jnp.int32)  # dup id 3 x3

    g = jax.grad(lambda t: jnp.sum(emb_ops.gather_rows(t, ids) ** 2))(t)
    g_take = jax.grad(lambda t: jnp.sum(jnp.take(t, ids, axis=0) ** 2))(t)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_take), rtol=1e-6)

    tb = t.astype(jnp.bfloat16)
    gb = jax.grad(
        lambda t: jnp.sum(emb_ops.gather_rows(t, ids).astype(jnp.float32) ** 2)
    )(tb)
    gb_take = jax.grad(
        lambda t: jnp.sum(jnp.take(t, ids, axis=0).astype(jnp.float32) ** 2)
    )(tb)
    assert gb.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(gb, np.float32), np.asarray(gb_take, np.float32))


def _check_compaction(sums, uids, sf, cf):
    """`_compact_sorted_duplicates`' contract against numpy: slot j holds
    the j-th distinct id and the sum of its rows; the slots after the last
    distinct id hold zero sums at int32max, where the stream's pad is, so
    the ids stay ascending to the end."""
    want_ids, inverse = np.unique(sf, return_inverse=True)
    want_sums = np.zeros((sf.size, cf.shape[1]), np.float32)
    np.add.at(want_sums, inverse, cf)
    k = want_ids.size
    np.testing.assert_array_equal(uids[:k], want_ids)
    assert (uids[k:] == np.iinfo(np.int32).max).all()
    np.testing.assert_allclose(sums, want_sums, rtol=1e-6, atol=1e-6)
    assert not sums[k:].any()


@pytest.mark.parametrize(
    "ids_np",
    [
        np.asarray([[3, 3, 7], [0, 127, 3]], np.int32),   # duplicates
        np.arange(12, dtype=np.int32).reshape(3, 4),       # all distinct
        np.zeros((4, 4), np.int32),                        # one id repeated
        np.asarray([[127, 0, 64]], np.int32),              # unsorted extremes
    ],
)
def test_compact_sorted_duplicates_matches_numpy(ids_np):
    """The dedupe path's compaction (sorted boundary cumsum -> per-run
    sums; run starts sorted once more -> per-run id) must equal
    `np.unique` + `np.add.at` across duplicate-heavy, distinct and
    degenerate id patterns, trailing empty slots included."""
    r = np.random.RandomState(ids_np.size)
    cf = r.randn(ids_np.size, 16).astype(np.float32)
    cf_sorted, sf = emb_ops._sorted_stream(
        jnp.asarray(ids_np.reshape(-1)), jnp.asarray(cf))
    sums, uids = jax.jit(emb_ops._compact_sorted_duplicates)(cf_sorted, sf)
    assert sums.shape == cf.shape and uids.dtype == jnp.int32
    _check_compaction(np.asarray(sums), np.asarray(uids),
                      np.asarray(sf), np.asarray(cf_sorted))


@pytest.mark.parametrize("ids_kind", ["oob_pad", "shard_sentinels"])
def test_compact_sorted_duplicates_out_of_range_ids(ids_kind):
    """Out-of-range ids (`embedding_lookup`'s int32max // 2, the manual
    path's 2 x shard rows) are the LAST run of the sorted stream: they
    take the last distinct slot, `dedupe_then_place`'s remap sends that
    slot to int32max beside the empty ones, the remapped ids stay
    ascending (the block starts are searched in them), and `sums` slot j
    is still `uids` slot j's run."""
    r = np.random.RandomState(5)
    n, rows, d = 5000, 900, 11
    ids = _stream_case_ids(ids_kind, n, rows, r)
    cf = np.where((ids < rows)[:, None], r.randn(n, d), 0).astype(np.float32)
    cf_sorted, sf = emb_ops._sorted_stream(jnp.asarray(ids), jnp.asarray(cf))
    sums, uids = jax.jit(emb_ops._compact_sorted_duplicates)(cf_sorted, sf)
    sums, uids = np.asarray(sums), np.asarray(uids)
    _check_compaction(sums, uids, np.asarray(sf), np.asarray(cf_sorted))
    k = np.unique(ids).size
    assert uids[k - 1] == ids.max() >= rows and uids[k - 2] < rows
    remapped = np.where(uids >= rows, np.iinfo(np.int32).max, uids)
    assert (np.diff(remapped) >= 0).all()
    assert (remapped[k - 1:] == np.iinfo(np.int32).max).all()
    assert not sums[k - 1:].any()
    want = np.zeros((rows, d), np.float32)
    np.add.at(want, ids[ids < rows], cf[ids < rows])
    got = np.zeros((rows, d), np.float32)
    got[remapped[:k - 1]] = sums[:k - 1]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize(
    "ids_np",
    [
        np.random.RandomState(1).randint(0, 300, (64, 81)).astype(np.int32),
        np.full((64, 81), 7, np.int32),    # extreme skew -> window overflow
        np.asarray([[0, 299, 150]], np.int32),   # small N -> flat branch
    ],
)
def test_gather_rows_tiled_backward_matches_xla(monkeypatch, ids_np):
    """The tiled route (what a large table takes where the kernel cannot
    run): the fast-zone scan backward must equal the plain take VJP on (a)
    the scan path (uniform ids, table larger than 2 tiles), (b) the
    lax.cond overflow fallback (every id identical, so one window can't
    hold its tile's population), and (c) the small-batch flat route.
    TILE_ROWS = 64 shrinks tiles so a 300-row table exercises the real
    scan machinery on CPU."""
    monkeypatch.setattr(emb_ops, "TILE_ROWS", 64)
    assert emb_ops.backward_route(ids_np.size, 300, False) == (
        "tiled" if ids_np.size >= 4096 else "flat")
    t = jnp.asarray(np.random.RandomState(0).randn(300, 4), jnp.float32)
    ids = jnp.asarray(ids_np)
    g = jax.grad(lambda t: jnp.sum(emb_ops.gather_rows(t, ids) ** 2))(t)
    g_ref = jax.grad(lambda t: jnp.sum(jnp.take(t, ids, axis=0) ** 2))(t)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-6)

    # bf16 table round-trips through the f32 accumulator
    tb = t.astype(jnp.bfloat16)
    gb = jax.grad(
        lambda t: jnp.sum(emb_ops.gather_rows(t, ids).astype(jnp.float32) ** 2)
    )(tb)
    assert gb.dtype == jnp.bfloat16


def test_tiled_backward_on_manual_shard_path(monkeypatch, mesh8):
    """Code-review r5 pt3 regression: the manual shard_map schedule feeds
    gather_rows sentinel ids (the gathered schedule up to 7/8 of the batch
    on eight shards; the routed one, which mesh8 takes, its empty bucket
    slots: 8 x 512 slots for a device's 208 ids).
    The tiled backward must (a) stay exact and (b) keep those sentinels
    out of every tile's window population — mapping them to row 0 (the
    old behavior) piled them into tile 0 and permanently tripped the flat
    fallback. Tiny tiles, and no least stream length, force the real scan
    path on an 8-shard table."""
    V, D = 2048, 8     # 256 rows/shard on mesh8 > 2*16 -> tiled path
    table_np, table = make_table(mesh8, V=V, D=D, seed=11)
    ids_np = np.random.RandomState(12).randint(0, V, (64, 26)).astype(np.int32)
    ids = jax.device_put(ids_np, NamedSharding(mesh8, P("data", None)))
    w_np = np.random.RandomState(13).randn(64, 26, D).astype(np.float32)

    stream = 8 * emb_ops.route_cap(64 * 26 // 8, 8)
    with _route(monkeypatch, "tiled", stream, V // 8), jax.set_mesh(mesh8):
        g = jax.jit(
            jax.grad(
                lambda t: jnp.sum(
                    emb_ops.embedding_lookup(t, ids, mode="manual") * w_np
                )
            )
        )(table)

    expected = np.zeros_like(table_np)
    np.add.at(expected, ids_np.reshape(-1), w_np.reshape(-1, D))
    np.testing.assert_allclose(np.asarray(g), expected, rtol=1e-5, atol=1e-6)


def _stream_case_ids(ids_kind, n, rows, r):
    """Id patterns the backward's sorted stream has to keep in order."""
    if ids_kind == "zipf_runs":        # a few ids hold most of the stream
        ids = np.minimum(r.zipf(1.1, n) - 1, rows - 1)
    elif ids_kind == "all_equal":
        ids = np.full(n, 7)
    elif ids_kind == "oob_pad":        # embedding_lookup's int32max // 2
        ids = np.where(r.rand(n) < 0.3, np.iinfo(np.int32).max // 2,
                       r.randint(0, rows, n))
    elif ids_kind == "shard_sentinels":   # the manual path's 2 x rows
        ids = np.where(r.rand(n) < 0.75, 2 * rows, r.randint(0, rows, n))
    else:
        assert ids_kind == "unsigned"
        return r.randint(0, rows, n).astype(np.uint32)
    return ids.astype(np.int32)


@pytest.mark.parametrize("d", [11, 16, 17])
@pytest.mark.parametrize(
    "ids_kind", ["zipf_runs", "all_equal", "oob_pad", "shard_sentinels",
                 "unsigned"])
def test_sorted_stream_is_the_argsort_permutation(d, ids_kind):
    """The backward's stream: the one sort's own sorted ids and the rows
    gathered by its positions are EXACTLY what `argsort` and two gathers
    gave — same ids, same rows, duplicates in batch order."""
    r = np.random.RandomState(71)
    n, rows = 5000, 900
    ids = jnp.asarray(_stream_case_ids(ids_kind, n, rows, r))
    cf = jnp.asarray(r.randn(n, d).astype(np.float32))
    cf_sorted, sf = jax.jit(emb_ops._sorted_stream)(ids, cf)
    order = jnp.argsort(ids)
    assert cf_sorted.shape == (n, d) and sf.dtype == ids.dtype
    np.testing.assert_array_equal(np.asarray(sf), np.asarray(ids[order]))
    np.testing.assert_array_equal(np.asarray(cf_sorted), np.asarray(cf[order]))


def _tier_cap(case, n):
    return emb_ops.distinct_caps(n)[int(case[-1])]


@contextlib.contextmanager
def _spy_on_deduped_branches(monkeypatch):
    """The buffers (in rows) of the deduped branches that RUN inside: a
    branch is the one caller of `_sorted_runs` in the forward, and its
    buffer is the next table gather traced."""
    ran = []
    sorted_runs, take = emb_ops._sorted_runs, jnp.take

    def spy(sf):
        seg, uids = sorted_runs(sf)

        def spy_take(table, ids, axis):
            rows = ids.shape[0]
            jax.debug.callback(lambda: ran.append(rows))
            monkeypatch.setattr(jnp, "take", take)
            return take(table, ids, axis=axis)

        monkeypatch.setattr(jnp, "take", spy_take)
        return seg, uids

    monkeypatch.setattr(emb_ops, "_sorted_runs", spy)
    try:
        yield ran
    finally:
        monkeypatch.setattr(emb_ops, "_sorted_runs", sorted_runs)
        monkeypatch.setattr(jnp, "take", take)


def _lookup_case(case, route):
    """(rows, ids) of one stream of `gather_rows`' forward; `rows` is the
    table's."""
    r = np.random.RandomState(zlib.crc32(case.encode()))
    rows, n = 4096, 8192
    if case == "zipf":                   # few distinct ids: deduped
        ids = np.minimum(r.zipf(1.1, n) - 1, rows - 1) * 977 % rows
    elif case == "uniform":              # more distinct ids than the buffer
        ids = r.randint(0, rows, n)
    elif case == "all_equal":
        ids = np.full(n, 7)
    elif case == "both_sentinels":       # embedding_lookup's and a shard's
        ids = np.minimum(r.zipf(1.1, n) - 1, rows - 1) * 977 % rows
        ids = np.where(r.rand(n) < 0.2, np.iinfo(np.int32).max // 2, ids)
        ids = np.where(r.rand(n) < 0.5, 2 * rows, ids)
    elif case.startswith("at_cap_"):     # exactly a buffer's rows: fits
        ids = np.resize(r.permutation(rows)[:_tier_cap(case, n)], n)
    elif case.startswith("over_cap_"):   # one more: the next buffer
        ids = np.resize(r.permutation(rows)[:_tier_cap(case, n) + 1], n)
    else:
        # the least stream that is sorted, and one id fewer; the gates as
        # they are, so only where the route needs no small block
        assert route == "tiled"
        n = emb_ops.SORTED_MIN_IDS - (case == "below_sorted_min")
        ids = np.minimum(r.zipf(1.1, n) - 1, rows - 1) * 977 % rows
    return rows, r.permutation(ids).astype(np.int32)


LOOKUP_CASES = [
    (route, case) for route in ("kernel", "tiled")
    for case in ("zipf", "uniform", "all_equal", "both_sentinels",
                 "at_cap_0", "over_cap_0", "at_cap_1", "over_cap_1")
] + [("tiled", "below_sorted_min"), ("tiled", "at_sorted_min")]


@pytest.mark.parametrize("route,case", LOOKUP_CASES)
def test_deduped_lookup_equals_take(monkeypatch, route, case):
    """`gather_rows`' forward is `jnp.take`'s rows to the bit on every
    branch of its `switch` — the deduped lookup with the least buffer of
    `distinct_caps(n)` that holds the step's distinct ids, the plain gather
    from one id over the last — and under it on a stream too short to
    sort; the branch that ran is the one the distinct count calls for; the gradient, which reads the forward's
    sort as its residual, is `jnp.take`'s VJP; and `scatter_add_dense`,
    which sorts for itself, places the same stream the same way."""
    rows, ids_np = _lookup_case(case, route)
    n, d = ids_np.size, 11
    r = np.random.RandomState(n)
    t = jnp.asarray(r.randn(rows, d), jnp.float32)
    w_np = r.randn(n, d).astype(np.float32)
    ids = jnp.asarray(ids_np)
    if case.endswith("sorted_min"):
        monkeypatch.setattr(emb_ops, "TILE_ROWS", 16)
        ctx = contextlib.nullcontext()
        want_route = "flat" if case == "below_sorted_min" else "tiled"
        assert emb_ops.backward_route(n, rows, False) == want_route
    else:
        ctx, want_route = _route(monkeypatch, route, n, rows), route

    with ctx:
        with _spy_on_deduped_branches(monkeypatch) as ran:
            # a new function each case: `jit` keeps a trace, and its spy
            out = jax.jit(lambda t, i: emb_ops.gather_rows(t, i))(t, ids)
            jax.block_until_ready(out)
            jax.effects_barrier()
        sorted_ids = jax.eval_shape(emb_ops._lookup, t, ids)[1]
        g = jax.jit(jax.grad(
            lambda t: jnp.sum(emb_ops.gather_rows(t, ids) * w_np)))(t)
        pushed = jax.jit(
            lambda i, c: emb_ops.scatter_add_dense(i, c, rows))(ids, w_np)
    assert (sorted_ids is not None) == (want_route != "flat")
    holds = [cap for cap in emb_ops.distinct_caps(n)
             if np.unique(ids_np).size <= cap][:1]
    assert ran == (holds if want_route != "flat" else []), (case, ran)

    # out-of-range ids read `jnp.take`'s fill, NaN, on every branch
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(jnp.take(t, ids, axis=0)))
    g_take = jax.grad(lambda t: jnp.sum(jnp.take(t, ids, axis=0) * w_np))(t)
    scale = np.abs(np.asarray(g_take)).max()
    atol = 2e-5 if want_route == "kernel" else 1e-6
    np.testing.assert_allclose(
        np.asarray(g) / scale, np.asarray(g_take) / scale, atol=atol)
    np.testing.assert_allclose(
        np.asarray(pushed) / scale, np.asarray(g_take) / scale, atol=atol)


@pytest.mark.parametrize("n,distinct,want", [
    (55296 * 26, 149_228, (179_712, 359_424)),   # xdeepfm-criteo: an eighth
    (8192 * 26, 40_111, (26_624, 53_248)),       # deepfm-criteo: a quarter
    (4 * 79_872, 37_591, (39_936, 79_872)),      # criteo1tb, a shard's slots
    (4096, None, (512, 1024)),
    (4097, None, (512, 1024)),
    (6000, None, (1024, 1536)),
])
def test_distinct_caps(n, distinct, want):
    """An eighth and a quarter of the stream in whole 512s, from the
    length alone; every cell's step (its distinct ids as the benchmark's
    generator draws them, a count) fits one of them."""
    assert emb_ops.distinct_caps(n) == want
    assert distinct is None or distinct <= want[-1]


def test_deduped_lookup_shares_one_sort_with_its_backward():
    """At xDeepFM's shape (abstract values, nothing runs) a training step's
    lookup and its gradient hold ONE stable sort of (ids, positions) — the
    forward's, which the backward reads as its residual — beside the two
    that are not of the ids: the runs of each position, keyed by position,
    and the keys-only sort of the run starts (in each deduped branch of the
    forward, once in the backward's dedupe branch). The table is gathered
    from once a branch: a buffer's rows, or all n in the overflow's."""
    from elasticdl_tpu.ops.pallas_attention import interpret_mode

    n, rows, d = 55296 * 26, 2_605_056, 11
    caps = emb_ops.distinct_caps(n)
    with interpret_mode():
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda t, i, w: jnp.sum(emb_ops.gather_rows(t, i) * w)))(
            jax.ShapeDtypeStruct((rows, d), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n, d), jnp.float32))
    eqns = list(_all_eqns(jaxpr.jaxpr))
    sorts = sorted(
        (len(e.invars), e.params["num_keys"], e.params["is_stable"])
        for e in eqns if e.primitive.name == "sort")
    assert sorts == (
        [(1, 1, False)] * (len(caps) + 1) + [(2, 1, False)] * len(caps)
        + [(2, 1, True)])
    table_gathers = sorted(
        e.outvars[0].aval.shape[0] for e in eqns
        if e.primitive.name == "gather"
        and e.invars[0].aval.shape == (rows, d))
    assert table_gathers == [*caps, n]
    for cap in caps:
        expands = [e for e in eqns if e.primitive.name == "gather"
                   and e.invars[0].aval.shape == (cap, d)]
        assert [e.outvars[0].aval.shape for e in expands] == [(n, d)]


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


@pytest.mark.parametrize("route,n,rows", [
    ("kernel", 55296 * 26, 2_605_056),      # xdeepfm-criteo
    ("kernel", 8192 * 26, 33_800_192),      # deepfm-criteo
    ("tiled", 55296 * 26, 2_605_056),
])
def test_backward_holds_no_stream_long_id_gather_or_scatter(route, n, rows):
    """At xDeepFM's shape (55 296 x 26 = 1 437 696 ids x 11 columns into
    2 605 056 rows) and deepfm's (212 992 ids into 33.8M rows; abstract
    values, nothing runs) the backward holds ONE stable sort, of (ids,
    positions), and none of the operations that cost it 102 ms a step
    there: no gather of N ids out of the N-long id vector (`flat[order]`,
    10.25 ms: the sort's own first output is that) and no scatter-add into
    an N-row output past the fast zone (the dedupe's run sums, 92.3 ms:
    `_run_sums` goes in chunks that fit). The kernel route moreover holds
    no scatter of any kind over the N-long id vector (the distinct ids'
    `segment_max` was a scatter-max, 12.6 ms: they are one keys-only sort
    of the run starts) and no loop but `_run_sums`' scan (the block starts'
    two `searchsorted` loops, 2 x 1.97 ms on deepfm: `_block_starts` is
    straight-line). None can come back unnoticed by a CPU-only check."""
    from elasticdl_tpu.ops import pallas_scatter as ps
    from elasticdl_tpu.ops.pallas_attention import interpret_mode

    d = 11
    # the kernel's route where it can run, the tiled one where it cannot
    with interpret_mode() if route == "kernel" else contextlib.nullcontext():
        assert emb_ops.backward_route(n, rows, ps.runnable()) == route
        jaxpr = jax.make_jaxpr(
            lambda ids, cf: emb_ops.scatter_add_dense(ids, cf, rows))(
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n, d), jnp.float32))
    eqns = list(_all_eqns(jaxpr.jaxpr))
    sorts = sorted(
        (len(e.invars), e.params["num_keys"], e.params["is_stable"])
        for e in eqns if e.primitive.name == "sort")
    assert sorts[-1] == (2, 1, True)
    # beside it at most the dedupe's keys-only sort of the run starts
    assert [s[0] for s in sorts[:-1]] == [1] * (route == "kernel")
    id_gathers = [
        e for e in eqns if e.primitive.name == "gather"
        and e.invars[0].aval.shape == (n,)
        and e.outvars[0].aval.shape == (n,)]
    assert not id_gathers
    run_sums_chunks = n * 512 > emb_ops.FAST_SCATTER_BYTES
    stream_scatters = [
        e for e in eqns if e.primitive.name == "scatter-add"
        and e.invars[0].aval.shape == (n, d)]
    # deepfm's stream fits the fast zone: its run sums are one segment_sum
    assert len(stream_scatters) == (
        route == "kernel" and not run_sums_chunks)
    assert any(e.primitive.name == "pallas_call" for e in eqns) == (
        route == "kernel")
    if route != "kernel":     # `tiled` keeps its searchsorted: no cell
        return
    id_scatters = [
        e for e in eqns if e.primitive.name.startswith("scatter")
        and any(v.aval.shape == (n,)
                and jnp.issubdtype(v.aval.dtype, jnp.integer)
                for v in e.invars)]
    assert not id_scatters
    loops = [e for e in eqns if e.primitive.name in ("while", "scan")]
    assert [e.primitive.name for e in loops] == ["scan"] * run_sums_chunks
    # that scan is `_run_sums`': it consumes the (chunks, rows, d) stream
    assert all(any(v.aval.shape[2:] == (d,) for v in e.invars)
               for e in loops)


@pytest.mark.parametrize("n,chunk_rows", [
    (8192 * 26, None),        # deepfm-criteo: one segment_sum, as before
    (32768 * 26, 212992),     # a shard of deepfm-criteo1tb: four chunks
    (55296 * 26, 205392),     # xdeepfm-criteo: seven, the last one padded
])
def test_run_sums_route_at_the_benchmark_streams(n, chunk_rows):
    """Which route a stream takes is decided by its shape alone (abstract
    values, nothing runs): up to `FAST_SCATTER_BYTES` of 512-byte rows one
    segment_sum over the whole stream, beyond it equal chunks that fit."""
    jaxpr = jax.make_jaxpr(emb_ops._run_sums)(
        jax.ShapeDtypeStruct((n, 11), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.int32))
    scatters = [e.invars[0].aval.shape[0] for e in _all_eqns(jaxpr.jaxpr)
                if e.primitive.name == "scatter-add"]
    assert scatters == [chunk_rows or n]
    assert ("scan" in str(jaxpr)) == bool(chunk_rows)


@pytest.mark.parametrize("n,d,fast_rows", [
    (5000, 11, 1000),       # five whole chunks
    (5000, 11, 999),        # six chunks, the last one padded
    (4096, 17, 64),         # runs far longer than a chunk
    (3000, 130, 500),       # rows wider than one 128-lane line
    (777, 3, 1000),         # one chunk: the plain segment_sum
])
def test_run_sums_in_chunks_equal_one_segment_sum(monkeypatch, n, d, fast_rows):
    """The dedupe's per-run sums, scanned in chunks that fit the fast
    zone, are the one segment_sum's to the bit: a run that straddles a
    chunk edge goes on adding in stream order."""
    r = np.random.RandomState(n + d)
    sf = np.sort(np.minimum(r.zipf(1.1, n) - 1, 900)).astype(np.int32)
    seg = jnp.asarray(np.cumsum(np.r_[True, sf[1:] != sf[:-1]]) - 1)
    cf = jnp.asarray(r.randn(n, d).astype(np.float32))
    want = jax.ops.segment_sum(cf, seg, num_segments=n,
                               indices_are_sorted=True)
    monkeypatch.setattr(
        emb_ops, "FAST_SCATTER_BYTES", fast_rows * 512 * -(-d // 128))
    jaxpr = jax.make_jaxpr(emb_ops._run_sums)(cf, seg)
    assert ("scan" in str(jaxpr)) == (n > fast_rows)
    got = jax.jit(lambda a, b: emb_ops._run_sums(a, b))(cf, seg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _block_starts_case(kind, r):
    """(sorted int32 ids, rows, block) for test_block_starts_equal_searchsorted."""
    imax = np.iinfo(np.int32).max
    n, rows, bs = 5000, 16384, 256
    if kind == "zipf_runs":
        ids = np.minimum(r.zipf(1.1, n) - 1, rows - 1)
    elif kind == "all_equal":
        ids = np.full(n, 4321)
    elif kind == "empty_blocks_in_the_middle":
        ids = np.where(r.rand(n) < 0.5, r.randint(0, bs, n),
                       r.randint(rows - 3 * bs, rows - 2 * bs, n))
    elif kind == "all_in_the_last_block":
        ids = r.randint(rows - bs, rows, n)
    elif kind == "int32max_tail":        # the deduped stream's empty slots
        ids = np.where(np.arange(n) < 1500, r.randint(0, rows, n), imax)
    elif kind == "oob_pad":
        ids = np.where(r.rand(n) < 0.3, imax // 2, r.randint(0, rows, n))
    elif kind == "shard_sentinels":
        ids = np.where(r.rand(n) < 0.75, 2 * rows, r.randint(0, rows, n))
    elif kind == "negative_pads":        # sort first, before block 0
        ids = np.where(r.rand(n) < 0.2, -1, r.randint(0, rows, n))
    elif kind == "rows_not_whole_blocks":
        rows = 16384 - 100
        ids = r.randint(0, rows, n)
    elif kind == "below_one_line":
        ids = r.randint(0, rows, 50)
    elif kind == "one_id":
        ids = np.asarray([rows - 1])
    elif kind == "not_whole_lines":
        ids = r.randint(0, rows, 5001)
    else:
        assert kind == "wider_line"      # 70 001 ids: lines of 256
        ids = np.minimum(r.zipf(1.1, 70001) - 1, rows - 1)
    return np.sort(ids).astype(np.int32), rows, bs


@pytest.mark.parametrize("kind", [
    "zipf_runs", "all_equal", "empty_blocks_in_the_middle",
    "all_in_the_last_block", "int32max_tail", "oob_pad", "shard_sentinels",
    "negative_pads", "rows_not_whole_blocks", "below_one_line", "one_id",
    "not_whole_lines", "wider_line"])
def test_block_starts_equal_searchsorted(kind):
    """`_block_starts` IS `searchsorted(ids, arange(0, vpad + 1, bs))`,
    side "left", on every stream the kernel route can see: the raw sorted
    ids and the deduped ones with their int32max tail."""
    ids, rows, bs = _block_starts_case(kind, np.random.RandomState(17))
    vpad = -(-rows // bs) * bs
    got = jax.jit(emb_ops._block_starts, static_argnums=(1, 2))(
        jnp.asarray(ids), vpad, bs)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(got), np.searchsorted(ids, np.arange(0, vpad + 1, bs)))


@pytest.mark.parametrize("n,rows,line", [
    (8192 * 26, 33_800_192, 512),       # deepfm-criteo: 16 504 blocks
    (32768 * 26, 23_472_128, 1024),     # deepfm-criteo1tb, a shard: 11 461
    (55296 * 26, 2_605_056, 1024),      # xdeepfm-criteo: 1 272
])
def test_block_starts_at_the_benchmark_streams(n, rows, line):
    """At the benchmark's three streams (abstract values, nothing runs)
    the block starts are straight-line code: no loop, no sort, no scatter;
    one gather, of a whole line of about sqrt(n) ids per block."""
    from elasticdl_tpu.ops import pallas_scatter as ps

    bs = ps.BLOCK_ROWS
    jaxpr = jax.make_jaxpr(
        lambda ids: emb_ops._block_starts(ids, rows, bs))(
        jax.ShapeDtypeStruct((n,), jnp.int32))
    assert [v.aval.shape for v in jaxpr.jaxpr.outvars] == [(rows // bs + 1,)]
    eqns = list(_all_eqns(jaxpr.jaxpr))
    names = {e.primitive.name for e in eqns}
    assert not names & {"while", "scan", "sort", "cond"}
    assert not any(name.startswith("scatter") for name in names)
    assert [e.outvars[0].aval.shape for e in eqns
            if e.primitive.name == "gather"] == [(rows // bs + 1, line)]


def _block_firsts_and_pops(ids_np, V, bs):
    """What `_pallas_table_grad` sees of a stream: per block, the column
    of its first id in sorted order and how many ids it holds."""
    sf = np.sort(ids_np[ids_np >= 0].reshape(-1))
    edges = np.searchsorted(sf, np.arange(0, V + 1, bs))
    return edges[:-1], np.diff(edges)


def _pallas_case_ids(ids_kind, r):
    """(V, ids) for test_pallas_backward_matches_reference, block 256."""
    bs = 256
    if ids_kind == "offset127_w256":
        # w = 256 (the large tables' window): block 0 holds 127 ids, so
        # block 1's ids start 127 columns into its window, straddle the
        # 128 boundary and fill all the room a window has
        V, pops = 16384, [127, 128] + [62] * 59 + [61] * 3
    elif ids_kind == "odd_lanes_window":
        # w = 768, room for 640 = two 256-wide chunks and a 128-wide tail:
        # block 6's ids start 2 columns into its window and fill the room
        V, pops = 4096, [427] * 6 + [640] + [427] * 4 + [426] * 5
    else:
        V = 2048
        ids_np = r.randint(0, V, (64, 81)).astype(np.int32)
        if ids_kind.startswith("skewed"):
            ids_np[:, :60] = 7      # hot id -> window overflow -> fallback
        elif ids_kind == "with_padding":
            ids_np[:, 60:] = -1
        return V, ids_np
    ids_np = np.concatenate([
        b * bs + np.arange(p) % bs for b, p in enumerate(pops)])
    return V, r.permutation(ids_np).astype(np.int32).reshape(64, -1)


@pytest.mark.parametrize("d", [16, 17])
@pytest.mark.parametrize(
    "ids_kind", ["uniform", "skewed", "skewed_chunked_sums", "with_padding",
                 "offset127_w256", "odd_lanes_window"])
def test_pallas_backward_matches_reference(monkeypatch, d, ids_kind):
    """The kernel route: the MXU one-hot placement kernel must match a
    host reference across (a) uniform ids
    (the kernel path), (b) extreme skew (the dedupe branch, its run sums
    as one segment_sum and, past `FAST_SCATTER_BYTES`, in chunks of 1000
    rows), (c) negative padding ids, and (d, e) streams built to sit on the
    window's edges while still passing the guard — at D=16 (aligned) AND
    D=17 (the deepfm merged-linear-column depth, which exercises the
    sublane padding and the in-kernel d_out slice). Runs the REAL Mosaic
    kernel in interpret mode on CPU; tolerance reflects the two-term bf16
    split (~4e-6 rel). Small blocks force several grid steps and windows
    of an odd number of 128s (the ragged-tail truncation bug class, caught
    on-TPU in round 5)."""
    from elasticdl_tpu.ops import pallas_scatter as ps
    from elasticdl_tpu.ops.pallas_attention import interpret_mode

    monkeypatch.setattr(ps, "BLOCK_ROWS", 256)
    if ids_kind == "skewed_chunked_sums":
        monkeypatch.setattr(emb_ops, "FAST_SCATTER_BYTES", 1000 * 512)
    r = np.random.RandomState(31)
    V, ids_np = _pallas_case_ids(ids_kind, r)
    w = ps.window_cols(ids_np.size, V, 256, 1.3)
    firsts, pops = _block_firsts_and_pops(ids_np, V, 256)
    if ids_kind == "offset127_w256":
        assert (w, firsts[1] % 128, pops[1], pops.max()) == (256, 127, 128, 128)
    elif ids_kind == "odd_lanes_window":
        assert (w, firsts[6] % 128, pops[6], pops.max()) == (768, 2, 640, 640)
    t = jnp.asarray(r.randn(V, d) * 0.1, jnp.float32)
    w_np = r.randn(*ids_np.shape, d).astype(np.float32)

    with interpret_mode():
        g = jax.jit(jax.grad(
            lambda t: jnp.sum(
                emb_ops.embedding_lookup(t, jnp.asarray(ids_np), mode="auto")
                * w_np)
        ))(t)

    expected = np.zeros((V, d), np.float32)
    m = ids_np >= 0
    np.add.at(expected, ids_np[m], w_np[m])
    scale = np.abs(expected).max()
    np.testing.assert_allclose(
        np.asarray(g) / scale, expected / scale, atol=2e-5)


@pytest.mark.parametrize("n,rows,want", [
    (8192 * 26, 1_300_000 * 26, 256),       # deepfm-criteo.resident and .job
    (32768 * 26, 3_611_000 * 26 // 4, 256),  # deepfm-criteo1tb, a shard of 4
    (55296 * 26, 2_605_056, 1664),          # xdeepfm-criteo.resident
    (4096, 2048, 4096 + 128),               # never more than the stream
    (100, 1 << 30, 256),                    # never less than 128 + the slop
])
def test_pallas_window_cols(n, rows, want):
    """The window is derived from what the code sees — ids, rows, block —
    in whole 128s (the DMA's and the MXU's granularity, not the loop's
    256-wide chunk): the benchmark's three tables get 256 / 256 / 1664
    columns, and any window covers `slack` x the mean block population
    after the up-to-127-column slop of its aligned start."""
    from elasticdl_tpu.ops import pallas_scatter as ps

    w = ps.window_cols(n, rows, 2048, 1.3)
    assert w == want
    assert w % 128 == 0
    assert w >= min(n, 1.3 * n * 2048 / rows) + 127


@pytest.mark.parametrize("w,pops", [
    (256, [127, 128]),          # first id 127 columns in; one 128-wide pass
    (512, [300, 384]),          # first id 44 columns in; a chunk and a tail
    (1664, [1500, 1536]),       # 13 x 128: first id 92 columns in; six chunks
])
def test_place_sorted_grads_window_edges(w, pops):
    """The kernel alone, at the benchmark's two window widths and one
    between: a block that fills its window's room, from a first id that is not on a 128
    boundary, places every row, and the columns before its first id (the
    block before) or past its last (the block after) place none."""
    from elasticdl_tpu.ops import pallas_scatter as ps

    bs, d = 256, 8
    r = np.random.RandomState(w)
    sf = np.concatenate(
        [b * bs + np.sort(r.randint(0, bs, p)) for b, p in enumerate(pops)]
    ).astype(np.int32)
    firsts = np.searchsorted(sf, np.arange(0, len(pops) * bs, bs))
    assert firsts[1] % 128 and max(pops) == w - 128
    cf = r.randn(sf.size, d).astype(np.float32)
    args = (
        jnp.asarray(np.concatenate([cf.T, np.zeros((d, w), np.float32)], 1)),
        jnp.asarray(np.concatenate(
            [sf, np.full(w, np.iinfo(np.int32).max, np.int32)])[None, :]),
        jnp.asarray(firsts, jnp.int32))
    out = ps.place_sorted_grads(
        *args, num_rows=len(pops) * bs, block_rows=bs, w=w, interpret=True)
    ref = _scatter_ref(sf, cf, len(pops) * bs)
    scale = np.abs(ref).max()
    np.testing.assert_allclose(
        np.asarray(out).T / scale, ref / scale, atol=2e-5)
    with pytest.raises(ValueError, match="multiple of 128"):
        ps.place_sorted_grads(
            *args, num_rows=len(pops) * bs, block_rows=bs, w=w - 64,
            interpret=True)


def test_pallas_backward_clustered_distinct_ids_flat_branch(monkeypatch):
    """Reach the FINAL flat placement branch (code-review r5 pt6): the
    dedupe middle path collapses duplicate-driven skew, so only >w
    DISTINCT ids clustered inside one output block can overflow both
    guards. Shape math (default bs=2048): num_rows=16384, n=4096 ->
    w = 1024 windows; ~2000 distinct contiguous ids inside block 0
    exceed it after dedupe too, so placement must take the exact flat
    scatter — and still match the host reference exactly."""
    from elasticdl_tpu.ops.pallas_attention import interpret_mode

    V = 16384
    r = np.random.RandomState(51)
    t = jnp.asarray(r.randn(V, 8) * 0.1, jnp.float32)
    ids_np = (100 + (np.arange(4096) % 2000)).astype(np.int32).reshape(64, 64)
    w_np = r.randn(64, 64, 8).astype(np.float32)

    with interpret_mode():
        g = jax.jit(jax.grad(
            lambda t: jnp.sum(
                emb_ops.embedding_lookup(t, jnp.asarray(ids_np), mode="auto")
                * w_np)
        ))(t)

    expected = np.zeros((V, 8), np.float32)
    np.add.at(expected, ids_np.reshape(-1), w_np.reshape(-1, 8))
    np.testing.assert_allclose(np.asarray(g), expected, rtol=1e-5, atol=1e-6)


def test_pallas_backward_on_manual_shard_path(monkeypatch, mesh8):
    """The backward must stay exact under the manual shard_map schedule
    with the kernel runnable, whose empty bucket slots (routed: 8 buckets
    of 512 for a device's 208 ids) arrive as 2*shard_rows sentinels. Its
    256-row shards stay under the kernel's gates and take the
    flat route: the Mosaic kernel in interpret mode INSIDE shard_map on
    the CPU mesh never returns (PERF.md §7), so the kernel under
    shard_map is proven by the AOT compile for v5e:2x2 and on the chips."""
    from elasticdl_tpu.ops import pallas_scatter as ps
    from elasticdl_tpu.ops.pallas_attention import interpret_mode

    monkeypatch.setattr(ps, "BLOCK_ROWS", 256)
    stream = 8 * emb_ops.route_cap(64 * 26 // 8, 8)
    assert emb_ops.backward_route(stream, 2048 // 8, True) == "flat"
    V, D = 2048, 8
    table_np, table = make_table(mesh8, V=V, D=D, seed=41)
    ids_np = np.random.RandomState(42).randint(0, V, (64, 26)).astype(np.int32)
    ids = jax.device_put(ids_np, NamedSharding(mesh8, P("data", None)))
    w_np = np.random.RandomState(43).randn(64, 26, D).astype(np.float32)

    with jax.set_mesh(mesh8), interpret_mode():
        g = jax.jit(
            jax.grad(
                lambda t: jnp.sum(
                    emb_ops.embedding_lookup(t, ids, mode="manual") * w_np
                )
            )
        )(table)

    expected = np.zeros_like(table_np)
    np.add.at(expected, ids_np.reshape(-1), w_np.reshape(-1, D))
    scale = np.abs(expected).max()
    np.testing.assert_allclose(
        np.asarray(g) / scale, expected / scale, atol=2e-5)


@pytest.mark.parametrize("route", ["kernel", "tiled", "flat"])
def test_gather_rows_backward_unsigned_ids_and_empty(monkeypatch, route):
    """Code-review r5: (a) uint32 ids must not break the dedupe path's
    signed empty-segment sentinel (`uids < 0` is vacuous on an unsigned
    dtype and would send every empty slot to row 0) — the stream passes
    the kernel's gates and overflows block 0's window with id 0, so on the
    kernel route the dedupe runs; (b) empty ids must give a zero gradient
    on every route, not a trace error."""
    V, n = 2048, 4096
    r = np.random.RandomState(5)
    t = jnp.asarray(r.randn(V, 4), jnp.float32)
    ids_np = r.randint(0, V, n).astype(np.uint32)
    ids_np[:2000] = 0            # id 0 present AND duplicated past a window
    ids_u = jnp.asarray(ids_np.reshape(64, 64))
    ids_i = ids_u.astype(jnp.int32)

    with _route(monkeypatch, route, n, V):
        g_u = jax.grad(
            lambda t: jnp.sum(emb_ops.gather_rows(t, ids_u) ** 2))(t)
        # empty ids: zero gradient, no trace error
        empty = jnp.zeros((0, 3), jnp.int32)
        g_e = jax.grad(lambda t: jnp.sum(emb_ops.gather_rows(t, empty)))(t)
    g_ref = jax.grad(lambda t: jnp.sum(jnp.take(t, ids_i, axis=0) ** 2))(t)
    scale = np.abs(np.asarray(g_ref)).max()
    np.testing.assert_allclose(
        np.asarray(g_u) / scale, np.asarray(g_ref) / scale,
        atol=2e-5 if route == "kernel" else 1e-6)
    np.testing.assert_array_equal(np.asarray(g_e), 0.0)


@pytest.mark.parametrize("fast_rows", [None, 16])
def test_compact_sorted_duplicates_inside_shard_map(
        monkeypatch, mesh8, fast_rows):
    """The dedupe's compaction as a shard of deepfm-criteo1tb runs it:
    inside `shard_map`, over every shard's view of ALL ids (non-owned ones
    as 2 x shard-rows sentinels with zero rows) — whole, and with its run
    sums scanned in chunks (48 ids in three chunks of 16 rows), whose
    carry has to take the shard_map's varying type. The CPU's only cover
    of that: the kernel route cannot run inside shard_map here."""
    if fast_rows:
        monkeypatch.setattr(emb_ops, "FAST_SCATTER_BYTES", fast_rows * 512)
    V, D = 256, 8
    shard_rows = V // 8
    ids_np = np.random.RandomState(8).randint(0, V, (16, 3)).astype(np.int32)
    w_np = np.random.RandomState(9).randn(16, 3, D).astype(np.float32)
    ids = jax.device_put(ids_np, NamedSharding(mesh8, P("data", None)))
    rows = jax.device_put(w_np, NamedSharding(mesh8, P("data", None, None)))

    def shard_fn(ids_local, rows_local):
        all_ids = jax.lax.all_gather(ids_local, "data", tiled=True)
        all_rows = jax.lax.all_gather(rows_local, "data", tiled=True)
        local = all_ids - jax.lax.axis_index("data") * shard_rows
        owned = (local >= 0) & (local < shard_rows)
        flat = jnp.where(owned, local, 2 * shard_rows).reshape(-1)
        cf = jnp.where(owned[..., None], all_rows, 0.0).reshape(-1, D)
        sums, uids = emb_ops._compact_sorted_duplicates(
            *emb_ops._sorted_stream(flat, cf))
        # per shard: (48, D) sums and (48,) ids, stacked along the rows
        return sums, uids

    with jax.set_mesh(mesh8):
        sums, uids = jax.jit(jax.shard_map(
            shard_fn, in_specs=(P("data", None), P("data", None, None)),
            out_specs=(P("data", None), P("data"))))(ids, rows)
    # (a fresh function: a trace of `_run_sums` itself at this shape may be
    # cached from the other case, made under another FAST_SCATTER_BYTES)
    jaxpr = jax.make_jaxpr(lambda rows, seg: emb_ops._run_sums(rows, seg))(
        jnp.zeros((48, D)), jnp.zeros((48,), jnp.int32))
    assert ("scan" in str(jaxpr)) == bool(fast_rows)

    sums = np.asarray(sums).reshape(8, 48, D)
    uids = np.asarray(uids).reshape(8, 48)
    got = np.zeros((V, D), np.float32)
    for shard in range(8):
        local = ids_np.reshape(-1) - shard * shard_rows
        owned = (local >= 0) & (local < shard_rows)
        keys = np.where(owned, local, 2 * shard_rows)
        order = np.argsort(keys, kind="stable")
        cf = np.where(owned[:, None], w_np.reshape(-1, D), 0.0)[order]
        _check_compaction(sums[shard], uids[shard], keys[order], cf)
        keep = (uids[shard] >= 0) & (uids[shard] < shard_rows)
        got[shard * shard_rows + uids[shard][keep]] += sums[shard][keep]
    expected = np.zeros((V, D), np.float32)
    np.add.at(expected, ids_np.reshape(-1), w_np.reshape(-1, D))
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mesh_name", ["mesh8", "mesh_4x2"])
@pytest.mark.parametrize("mode", ["manual", "auto"])
def test_lookup_matches_dense(mesh_name, mode, request):
    mesh = request.getfixturevalue(mesh_name)
    table_np, table = make_table(mesh)
    ids_np = np.random.RandomState(1).randint(0, 512, (16, 5)).astype(np.int32)
    ids = jax.device_put(ids_np, NamedSharding(mesh, P("data", None)))

    with jax.set_mesh(mesh):
        out = jax.jit(lambda t, i: emb_ops.embedding_lookup(t, i, mode=mode))(table, ids)
    np.testing.assert_allclose(np.asarray(out), table_np[ids_np], rtol=1e-6)


@pytest.mark.parametrize("mesh_name", ["mesh8", "mesh_4x2"])
def test_gradients_match_dense(mesh_name, request):
    mesh = request.getfixturevalue(mesh_name)
    table_np, table = make_table(mesh, V=256, D=8)
    ids_np = np.random.RandomState(2).randint(0, 256, (16, 3)).astype(np.int32)
    ids = jax.device_put(ids_np, NamedSharding(mesh, P("data", None)))
    w_np = np.random.RandomState(3).randn(16, 3, 8).astype(np.float32)

    def loss(t, mode):
        return jnp.sum(emb_ops.embedding_lookup(t, ids, mode=mode) * w_np)

    with jax.set_mesh(mesh):
        g_manual = jax.jit(jax.grad(lambda t: loss(t, "manual")))(table)
        g_auto = jax.jit(jax.grad(lambda t: loss(t, "auto")))(table)

    expected = np.zeros_like(table_np)
    for b in range(16):
        for l in range(3):
            expected[ids_np[b, l]] += w_np[b, l]
    np.testing.assert_allclose(np.asarray(g_manual), expected, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g_auto), expected, rtol=1e-5)
    # gradient keeps the table's row sharding (no host round-trip)
    assert g_manual.sharding.spec[0] == tuple(mesh.axis_names) or len(mesh.axis_names) == 1


def test_padding_ids_give_zero(mesh8):
    _, table = make_table(mesh8, V=256, D=8)
    ids_np = np.full((8, 4), -1, np.int32)
    ids_np[:, 0] = 3
    with jax.set_mesh(mesh8):
        out = jax.jit(lambda t, i: emb_ops.embedding_lookup(t, i))(table, jnp.asarray(ids_np))
    out = np.asarray(out)
    assert np.all(out[:, 1:] == 0)
    assert np.any(out[:, 0] != 0)


@pytest.mark.parametrize("route", ["tiled", "flat"])
def test_padding_ids_backward_zero_grad(monkeypatch, mesh8, route):
    """Pad slots (negative ids) must contribute ZERO gradient on every
    route the CPU mesh can run, through both lookup schedules — and on
    `tiled` they are routed to a large OOB sentinel, not row 0, so heavy
    bag padding can't overflow tile 0's window (code-review r5 pt4). Tiny
    tiles force the real scan path."""
    V, D = 2048, 8
    table_np, table = make_table(mesh8, V=V, D=D, seed=21)
    ids_np = np.random.RandomState(22).randint(0, V, (16, 6)).astype(np.int32)
    ids_np[:, 3:] = -1                      # half the bag is padding
    ids = jax.device_put(ids_np, NamedSharding(mesh8, P("data", None)))
    w_np = np.random.RandomState(23).randn(16, 6, D).astype(np.float32)

    expected = np.zeros_like(table_np)
    for b in range(16):
        for l in range(3):                  # only the real slots
            expected[ids_np[b, l]] += w_np[b, l]

    with _route(monkeypatch, route, 16 * 6, V // 8), jax.set_mesh(mesh8):
        for lookup_mode in ("manual", "auto"):
            g = jax.jit(
                jax.grad(
                    lambda t: jnp.sum(
                        emb_ops.embedding_lookup(t, ids, mode=lookup_mode)
                        * w_np
                    )
                )
            )(table)
            np.testing.assert_allclose(
                np.asarray(g), expected, rtol=1e-5, atol=1e-6,
                err_msg=f"{route}/{lookup_mode}")


def test_combiners():
    vecs = jnp.asarray(np.arange(24, dtype=np.float32).reshape(2, 3, 4))
    ids = jnp.asarray([[1, 2, -1], [5, -1, -1]], jnp.int32)
    s = emb_ops.combine(vecs, "sum", ids)
    m = emb_ops.combine(vecs, "mean", ids)
    expected_sum0 = np.asarray(vecs)[0, 0] + np.asarray(vecs)[0, 1]
    np.testing.assert_allclose(np.asarray(s)[0], expected_sum0)
    np.testing.assert_allclose(np.asarray(m)[0], expected_sum0 / 2)
    np.testing.assert_allclose(np.asarray(m)[1], np.asarray(vecs)[1, 0])


@pytest.mark.parametrize("mesh_name", ["mesh8", "mesh_4x2"])
def test_embedding_layer_in_model(mesh_name, request):
    """End-to-end: flax model with a sharded Embedding trains one step."""
    import flax.linen as nn
    import optax
    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    mesh = request.getfixturevalue(mesh_name)

    class TinyRec(nn.Module):
        @nn.compact
        def __call__(self, feats, training=False):
            emb = Embedding(input_dim=1000, output_dim=8, combiner="sum")(feats["cat"])
            x = jnp.concatenate([emb, feats["dense"]], axis=-1)
            return nn.Dense(1)(x).reshape(-1)

    spec = ModelSpec(
        model=TinyRec(),
        loss=lambda labels, out: optax.sigmoid_binary_cross_entropy(
            out, jnp.asarray(labels, jnp.float32).reshape(-1)
        ),
        optimizer=optax.adam(1e-2),
        dataset_fn=None,
        eval_metrics_fn=None,
    )
    trainer = Trainer(spec, mesh)

    def batch(seed):
        rng = np.random.RandomState(seed)
        return {
            "features": {
                "cat": rng.randint(0, 1000, (16, 4)).astype(np.int32),
                "dense": rng.randn(16, 3).astype(np.float32),
            },
            "labels": rng.randint(0, 2, (16,)).astype(np.float32),
            "mask": np.ones((16,), np.float32),
        }

    state = trainer.init_state(batch(0))
    # table is sharded over every mesh axis
    table = state.params["Embedding_0"]["table"]
    assert table.shape == (emb_ops.padded_vocab(1000), 8)
    spec0 = table.sharding.spec[0]
    flat = spec0 if isinstance(spec0, tuple) else (spec0,)
    assert set(flat) == set(mesh.axis_names)

    losses = []
    for i in range(15):
        state, logs = trainer.train_step(state, batch(i % 3))
        losses.append(float(logs["loss"]))
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_nondivisible_table_falls_back_to_auto_with_parity(mesh8):
    """Round-3 (VERDICT #7): a resized mesh whose shard count doesn't divide
    the table's padded vocab must silently fall back to the auto schedule in
    `manual` mode — with bit-level parity to dense, forward AND backward."""
    # 252 rows over 8 devices: 252 % 8 != 0 -> manual schedule impossible.
    # The fallback decision keys on shapes (rows % ambient shard count), not
    # the table's physical layout, so a replicated table exercises it; GSPMD
    # then places the lookup however it likes (uneven shards are its job).
    mesh = mesh8
    rng = np.random.RandomState(0)
    table_np = rng.randn(252, 8).astype(np.float32)
    table = jax.device_put(table_np, NamedSharding(mesh, P()))
    assert table.shape[0] % len(mesh.devices.flat) != 0
    ids_np = np.random.RandomState(5).randint(0, 252, (16, 3)).astype(np.int32)
    ids = jax.device_put(ids_np, NamedSharding(mesh, P("data", None)))
    w_np = np.random.RandomState(6).randn(16, 3, 8).astype(np.float32)

    with jax.set_mesh(mesh):
        out = jax.jit(
            lambda t, i: emb_ops.embedding_lookup(t, i, mode="manual")
        )(table, ids)
        g = jax.jit(
            jax.grad(
                lambda t: jnp.sum(
                    emb_ops.embedding_lookup(t, ids, mode="manual") * w_np
                )
            )
        )(table)

    np.testing.assert_allclose(np.asarray(out), table_np[ids_np], rtol=1e-6)
    expected = np.zeros_like(table_np)
    for b in range(16):
        for l in range(3):
            expected[ids_np[b, l]] += w_np[b, l]
    np.testing.assert_allclose(np.asarray(g), expected, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------- #
# The routed schedule (PR 45): a shard looks up only the ids it owns. Ids
# and rows are exchanged all-to-all over the data axis; a step whose ids
# overflow a (source, owner) bucket takes the gathered schedule.

ROUTED_B, ROUTED_L, ROUTED_V, ROUTED_D = 128, 64, 2048, 8


@pytest.mark.parametrize("n_local,n_shards,other_axes,want", [
    (8192 * 26, 4, (), "routed"),            # deepfm-criteo1tb.resident-dp4
    (8192 * 26, 8, ("model",), "gathered"),  # data=4 x model=2
    (8192 * 26, 1, (), "auto"),              # one device
    (10, 8, (), "routed"),                   # the tiny shapes of the tests
])
def test_owner_route(n_local, n_shards, other_axes, want):
    assert emb_ops.owner_route(n_local, n_shards, other_axes) == want


@pytest.mark.parametrize("n_local,n_shards,want", [
    (8192 * 26, 4, 79872),    # the cell: 4 x 79 872 = 0.375 of 851 968
    (1024, 8, 512),           # whole 512s
    (10, 8, 512),
    (512 * 8, 8, 1024),       # 1.5 x 512 = 768 -> 1024
])
def test_route_cap(n_local, n_shards, want):
    assert emb_ops.route_cap(n_local, n_shards) == want


@pytest.mark.parametrize("mesh_name,want", [
    ("mesh8", "routed"), ("mesh_4x2", "gathered"), ("one", "auto")])
def test_lookup_schedule_follows_the_ambient_mesh(mesh_name, want, request):
    """The schedule is chosen from the mesh the trace sees: all-to-all on a
    data-only mesh, the ids' all-gather where rows are sharded over `model`
    too, no shard_map at all on one device."""
    from elasticdl_tpu.parallel.mesh import build_mesh

    mesh = (build_mesh({"data": 1}, jax.devices()[:1]) if mesh_name == "one"
            else request.getfixturevalue(mesh_name))
    with jax.set_mesh(mesh):
        jaxpr = jax.make_jaxpr(
            lambda t, i: emb_ops.embedding_lookup(t, i, mode="manual"))(
            jax.ShapeDtypeStruct((512, 8), jnp.float32),
            jax.ShapeDtypeStruct((16, 5), jnp.int32))
    names = {e.primitive.name for e in _all_eqns(jaxpr.jaxpr)}
    assert ("shard_map" in names) == (want != "auto")
    assert ("all_to_all" in names) == (want == "routed")
    assert ("cond" in names) == (want == "routed")
    assert ("all_gather" in names) == (want != "auto")   # the other branch


def _routed_case_ids(kind, r):
    """(B, L) ids on mesh8: source s holds rows [16 s, 16 s + 16), shard o
    owns table rows [256 o, 256 o + 256); a bucket's cap is 512 of a
    source's 1024 ids."""
    B, L, V = ROUTED_B, ROUTED_L, ROUTED_V
    rows_per = V // 8
    if kind == "uniform":
        return r.randint(0, V, (B, L))
    if kind == "zipf":
        return np.minimum(r.zipf(1.3, (B, L)) - 1, V - 1) * 977 % V
    if kind == "boundaries":
        edges = np.arange(0, V + 1, rows_per)
        return r.choice(
            np.clip(np.concatenate([edges - 1, edges, edges + 1]), 0, V - 1),
            (B, L))
    if kind == "padding":
        ids = r.randint(0, V, (B, L))
        ids[:, L // 4:] = -1
        ids[::3, 0] = V + 5          # past the table: a zero row as well
        return ids
    if kind == "one_shard":
        return r.randint(3 * rows_per, 4 * rows_per, (B, L))
    # source 2 sends shard 5 `cap - 1` / `cap + 1` ids, the rest elsewhere
    cap = emb_ops.route_cap(B // 8 * L, 8)
    ids = r.randint(0, 5 * rows_per, (B, L))
    mine = ids[32:48].reshape(-1)
    k = cap + (1 if kind == "over_cap" else -1)
    mine[:k] = r.randint(5 * rows_per, 6 * rows_per, k)
    ids[32:48] = r.permutation(mine).reshape(16, L)
    return ids


@pytest.mark.parametrize("kind", [
    "uniform", "zipf", "boundaries", "padding", "under_cap", "over_cap",
    "one_shard"])
def test_routed_lookup_matches_dense(monkeypatch, mesh8, kind):
    """Forward rows and table gradient of the manual lookup on a data-only
    mesh equal the dense numpy result for any id distribution, and the
    branch that ran is the one the counts call for: the exchange while every
    (source, owner) bucket holds its ids, the gathered schedule from one id
    over (nothing is dropped)."""
    B, L, V, D = ROUTED_B, ROUTED_L, ROUTED_V, ROUTED_D
    r = np.random.RandomState(zlib.crc32(kind.encode()))
    ids_np = _routed_case_ids(kind, r).astype(np.int32)
    table_np, table = make_table(mesh8, V=V, D=D, seed=51)
    w_np = r.randn(B, L, D).astype(np.float32)
    ids = jax.device_put(ids_np, NamedSharding(mesh8, P("data", None)))

    valid = (ids_np >= 0) & (ids_np < V)
    cap = emb_ops.route_cap(B // 8 * L, 8)
    fullest = max(
        np.bincount(src[ok] // (V // 8), minlength=8).max()
        for src, ok in zip(ids_np.reshape(8, -1), valid.reshape(8, -1)))
    if kind in ("under_cap", "over_cap"):
        assert fullest == cap + (1 if kind == "over_cap" else -1)
    if kind == "padding":      # a pad slot takes no room in any bucket
        assert valid.reshape(8, -1).sum(axis=1).max() < 1024 - cap

    ran = []
    unbucket = emb_ops._unbucket

    def spy(*args):
        jax.debug.callback(lambda: ran.append("routed"))
        return unbucket(*args)

    monkeypatch.setattr(emb_ops, "_unbucket", spy)
    with jax.set_mesh(mesh8):
        out, g = jax.jit(lambda t: (
            emb_ops.embedding_lookup(t, ids, mode="manual"),
            jax.grad(lambda t: jnp.sum(
                emb_ops.embedding_lookup(t, ids, mode="manual") * w_np))(t),
        ))(table)
        jax.block_until_ready((out, g))
        jax.effects_barrier()
    assert bool(ran) == (fullest <= cap), (kind, fullest, cap, len(ran))

    want = np.where(valid[..., None], table_np[np.where(valid, ids_np, 0)], 0)
    np.testing.assert_array_equal(np.asarray(out), want)
    expected = np.zeros_like(table_np)
    np.add.at(expected, ids_np[valid], w_np[valid])
    np.testing.assert_allclose(np.asarray(g), expected, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind,deduped_shards", [
    ("zipf", 4), ("uniform", 0), ("two_shards_uniform", 2)])
def test_routed_lookup_dedupes_on_each_shard(
        monkeypatch, mesh8, kind, deduped_shards):
    """The routed lookup on four devices with the owners' gathers on the
    deduped lookup (a table and a stream past the sorted routes' gates):
    rows and table gradient equal the unsharded numpy result, and each
    shard takes the branch ITS distinct ids call for — the branches hold
    no collective, so two shards may fetch distinct rows while two take
    the plain gather."""
    from elasticdl_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"data": 4}, list(mesh8.devices.flat)[:4])
    B, L, V, D = 256, 16, 8192, 8
    per = V // 4
    r = np.random.RandomState(zlib.crc32(kind.encode()))
    if kind == "zipf":
        ids_np = np.minimum(r.zipf(1.1, (B, L)) - 1, V - 1) * 977 % V
    else:
        ids_np = r.randint(0, V, (B, L))
        if kind == "two_shards_uniform":     # shards 2 and 3: four ids each
            ids_np = np.where(ids_np < 2 * per, ids_np, ids_np // per * per
                              + ids_np % 4)
    ids_np = ids_np.astype(np.int32)
    cap = emb_ops.route_cap(B // 4 * L, 4)
    stream = 4 * cap
    for shard in range(4):
        mine = ids_np[ids_np // per == shard]
        # the shard's stream: its ids and, in the empty slots, one sentinel
        assert (np.unique(mine).size + 1
                <= emb_ops.distinct_caps(stream)[-1]) == (
            kind == "zipf" or (kind == "two_shards_uniform" and shard >= 2))
    table_np, table = make_table(mesh, V=V, D=D, seed=61)
    w_np = r.randn(B, L, D).astype(np.float32)
    ids = jax.device_put(ids_np, NamedSharding(mesh, P("data", None)))

    with _route(monkeypatch, "tiled", stream, per), jax.set_mesh(mesh):
        with _spy_on_deduped_branches(monkeypatch) as ran:
            out = jax.jit(lambda t: emb_ops.embedding_lookup(
                t, ids, mode="manual"))(table)
            jax.block_until_ready(out)
            jax.effects_barrier()
        g = jax.jit(jax.grad(lambda t: jnp.sum(
            emb_ops.embedding_lookup(t, ids, mode="manual") * w_np)))(table)
    assert len(ran) == deduped_shards, (kind, ran)
    np.testing.assert_array_equal(np.asarray(out), table_np[ids_np])
    expected = np.zeros_like(table_np)
    np.add.at(expected, ids_np, w_np)
    np.testing.assert_allclose(np.asarray(g), expected, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind", ["uniform", "zipf", "piled", "gathered"])
def test_fenced_lookup_is_the_unfenced_reference_to_the_bit(
        monkeypatch, mesh8, mesh_4x2, kind):
    """`_fence_cotangent` (PR 61) is an identity whose backward is a
    barrier: rows and table gradient of the manual lookup equal `jnp.take`'s
    and its own VJP's BIT FOR BIT on the routed schedule's three id mixes —
    uniform (the owners' plain gather), Zipf (each distinct row once), the
    first source's ids piled on shard 0 (the overflow branch) — and on the
    gathered schedule of a data x model mesh; and the lookup with the fence
    taken out gives the same bits under cotangents of any value. The
    reference's cotangents are whole numbers: every sum of them is exact
    in float32 whatever its order, which the two scatters do not share."""
    from elasticdl_tpu.parallel.mesh import build_mesh

    mesh = (mesh_4x2 if kind == "gathered"
            else build_mesh({"data": 4}, list(mesh8.devices.flat)[:4]))
    shards = mesh.devices.size
    B, L, V, D = 256, 16, 8192, 8
    per = V // shards
    r = np.random.RandomState(zlib.crc32(f"fenced {kind}".encode()))
    if kind == "zipf":      # a field's ids Zipf over its own rows
        ranks = np.minimum(r.zipf(1.5, (B, L)) - 1, V // L - 1)
        ids_np = ranks * 40503 % (V // L) + np.arange(L) * (V // L)
    else:
        ids_np = r.randint(0, V, (B, L))
        if kind == "piled":
            ids_np[:B // 4] %= per
    ids_np = ids_np.astype(np.int32)
    if kind != "gathered":
        cap = emb_ops.route_cap(B // 4 * L, 4)
        fullest = max(np.bincount(src // per, minlength=4).max()
                      for src in ids_np.reshape(4, -1))
        assert (fullest > cap) == (kind == "piled")
        fits = [np.unique(ids_np[ids_np // per == o]).size + 1
                <= emb_ops.distinct_caps(4 * cap)[-1] for o in range(4)]
        assert all(fits) if kind == "zipf" else not any(fits)
    stream = B * L if kind in ("piled", "gathered") else 4 * cap
    table_np, table = make_table(mesh, V=V, D=D, seed=71)
    whole = r.randint(-8, 9, (B, L, D)).astype(np.float32)
    any_value = r.randn(B, L, D).astype(np.float32)
    ids = jax.device_put(ids_np, NamedSharding(mesh, P("data", None)))

    def rows_and_grads(lookup):
        # a new function each call: `jit` keeps a trace, and its fence
        return jax.jit(lambda t: (lookup(t), *(
            jax.grad(lambda t: jnp.sum(lookup(t) * w))(t)
            for w in (whole, any_value))))(table)

    manual = lambda t: emb_ops.embedding_lookup(t, ids, mode="manual")
    with _route(monkeypatch, "tiled", stream, per), jax.set_mesh(mesh):
        out, g_whole, g_any = rows_and_grads(manual)
        ref_out, ref_whole, _ = rows_and_grads(
            lambda t: jnp.take(t, ids, axis=0))
        monkeypatch.setattr(emb_ops, "_fence_cotangent", lambda t: t)
        plain_out, plain_whole, plain_any = rows_and_grads(manual)
    np.testing.assert_array_equal(np.asarray(out), table_np[ids_np])
    for got, want in ((out, ref_out), (g_whole, ref_whole),
                      (out, plain_out), (g_whole, plain_whole),
                      (g_any, plain_any)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mesh_name,barriers", [
    ("mesh8", 1), ("mesh_4x2", 1), ("one", 0)])
def test_the_table_s_cotangent_leaves_the_manual_lookup_behind_one_barrier(
        mesh_name, barriers, request):
    """One `optimization_barrier` in the backward of either schedule, on a
    value of the table shard's shape and outside every `cond` — so nothing
    that reads the gradient can be moved into the conditionals' branches —
    and none on one device, whose route calls `gather_rows` directly."""
    from elasticdl_tpu.parallel.mesh import build_mesh

    mesh = (build_mesh({"data": 1}, jax.devices()[:1]) if mesh_name == "one"
            else request.getfixturevalue(mesh_name))
    V, D = 512, 8
    with jax.set_mesh(mesh):
        jaxpr = jax.make_jaxpr(jax.grad(lambda t, i: jnp.sum(
            emb_ops.embedding_lookup(t, i, mode="manual"))))(
            jax.ShapeDtypeStruct((V, D), jnp.float32),
            jax.ShapeDtypeStruct((16, 5), jnp.int32))

    def fences(jaxpr, in_cond=False):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "optimization_barrier":
                yield in_cond, [v.aval.shape for v in eqn.outvars]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from fences(
                    sub, in_cond or eqn.primitive.name == "cond")

    assert list(fences(jaxpr.jaxpr)) == [
        (False, [(V // mesh.devices.size, D)])] * barriers


def test_owner_plan_and_unbucket_hold_no_scatter():
    """At the four-chip cell's shape (212 992 local ids, 4 shards, buckets
    of 79 872, 11 columns; abstract values, nothing runs) the plan is two
    sorts and compares, the buckets are contiguous slices, and `_unbucket`
    and its backward are one gather of 212 992 rows each: no scatter of an
    id or a row at a time (8-45 ns each on the chip), no loop."""
    n, shards, d = 8192 * 26, 4, 11
    cap = emb_ops.route_cap(n, shards)

    def fn(flat, buf, g):
        sf, order, starts, counts, slot = emb_ops._owner_plan(
            flat, 23_472_128, shards, cap)
        send = emb_ops._owner_buckets(sf, starts, counts, cap, 7)
        out, vjp = jax.vjp(
            lambda b: emb_ops._unbucket(b, slot, order, starts, counts), buf)
        return send, out, vjp(g)

    jaxpr = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((shards, cap, d), jnp.float32),
        jax.ShapeDtypeStruct((n, d), jnp.float32))
    assert [v.aval.shape for v in jaxpr.jaxpr.outvars] == [
        (shards, cap), (n, d), (shards, cap, d)]
    eqns = list(_all_eqns(jaxpr.jaxpr))
    names = [e.primitive.name for e in eqns]
    assert not [p for p in names if p.startswith("scatter")]
    assert not [p for p in names if p in ("while", "scan")]
    assert names.count("sort") == 2
    row_gathers = sorted(
        (e.invars[0].aval.shape, e.outvars[0].aval.shape) for e in eqns
        if e.primitive.name == "gather" and e.outvars[0].aval.ndim == 2)
    assert row_gathers == [
        ((n, d), (n, d)), ((shards * cap, d), (n, d))]
    # the buckets: one slice a shard of the ids and of the cotangent rows
    slices = [e.outvars[0].aval.shape for e in eqns
              if e.primitive.name == "dynamic_slice"
              and e.outvars[0].aval.shape[0] == cap]
    assert sorted(slices) == [(cap,)] * shards + [(cap, d)] * shards


# ---------------------------------------------------------------------- #
# scatter_add_dense — the embedding TIER's push hot path (ISSUE 10).
# The tier's owner stores route every deduped push through this entry,
# which takes gather_rows' backward routes — including the kernel's
# dedupe skew path — so its edges get pinned here: empty batch,
# all-duplicate ids, vocab-boundary ids, and parity across the routes.

ROUTES = ["kernel", "tiled", "flat"]


def _scatter_ref(ids_np, rows_np, num_rows):
    out = np.zeros((num_rows, rows_np.shape[-1]), np.float32)
    m = (ids_np >= 0) & (ids_np < num_rows)
    np.add.at(out, ids_np[m], rows_np[m])
    return out


@pytest.mark.parametrize("route", ROUTES)
def test_scatter_add_dense_empty_batch(monkeypatch, route):
    """A statically-empty push is a zero table whatever route its table
    would take (the tier's empty-batch call: a batch whose every id was a
    padding sentinel filtered client-side)."""
    with _route(monkeypatch, route, 1, 512):
        out = emb_ops.scatter_add_dense(
            jnp.zeros((0,), jnp.int32), jnp.zeros((0, 8), jnp.float32), 512)
    assert out.shape == (512, 8)
    assert np.all(np.asarray(out) == 0)


def test_scatter_add_dense_all_duplicate_ids_pallas_dedupe(monkeypatch):
    """Every id identical — the hardest skew: the kernel's window guard
    must overflow into the dedupe middle path (adjacent-duplicate
    compaction), which collapses the stream to ONE row before placement.
    Real Mosaic kernel in interpret mode; exactness vs the host
    reference within the two-term bf16 split's ~4e-6 rel."""
    V, n, d = 2048, 4096, 16
    r = np.random.RandomState(0)
    ids_np = np.full((n,), 513, np.int32)       # one hot id, mid-vocab
    rows_np = r.randn(n, d).astype(np.float32)
    with _route(monkeypatch, "kernel", n, V):
        out = jax.jit(
            emb_ops.scatter_add_dense, static_argnums=(2,)
        )(jnp.asarray(ids_np), jnp.asarray(rows_np), V)
    ref = _scatter_ref(ids_np, rows_np, V)
    scale = np.abs(ref).max()
    np.testing.assert_allclose(
        np.asarray(out) / scale, ref / scale, atol=2e-5)


@pytest.mark.parametrize("route", ROUTES)
def test_scatter_add_dense_vocab_boundary_ids(monkeypatch, route):
    """Boundary ids — 0, V-1 — must land; V, V+1, negatives (padding
    sentinels, the tier's pow2 padding) must drop on EVERY route: the
    boundary semantics must be identical on the chip and off it."""
    V, d = 512, 8
    ids_np = np.array([0, 0, V - 1, V, V + 7, -1, -5, 3], np.int32)
    rows_np = np.arange(8 * d, dtype=np.float32).reshape(8, d) + 1.0
    with _route(monkeypatch, route, 8, V):
        out = np.asarray(emb_ops.scatter_add_dense(
            jnp.asarray(ids_np), jnp.asarray(rows_np), V))
    ref = _scatter_ref(ids_np, rows_np, V)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    # the dropped rows contributed NOTHING anywhere
    assert out.sum() == pytest.approx(ref.sum(), rel=1e-5)


def test_scatter_add_dense_strategy_parity_skewed(monkeypatch):
    """All three routes agree on a skewed (30%-hot) stream — the parity
    the tier depends on when owner processes sit on different platforms
    (the kernel on a TPU, the tiled scan or the flat scatter off it)."""
    V, n, d = 2048, 4096, 16
    r = np.random.RandomState(1)
    ids_np = r.randint(0, V, n).astype(np.int32)
    ids_np[: n // 3] = 77                       # 30% hot id
    rows_np = r.randn(n, d).astype(np.float32)
    ref = _scatter_ref(ids_np, rows_np, V)
    for route in ROUTES:
        with monkeypatch.context() as patch, _route(patch, route, n, V):
            out = np.asarray(emb_ops.scatter_add_dense(
                jnp.asarray(ids_np), jnp.asarray(rows_np), V))
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4,
                                   err_msg=route)


RETIRED_VARIABLES = {
    "EDL_EMB_SCATTER": "sorted",
    "EDL_EMB_PALLAS_GROUP": "4",
    "EDL_EMB_PALLAS_PRECISION": "bf16",
    "EDL_EMB_PALLAS_BS": "4096",
    "EDL_EMB_TILE_ROWS": "64",
    "EDL_EMB_WINDOW_SLACK": "2.0",
}


def test_retired_variables_change_nothing(monkeypatch):
    """The traced backward reads no environment variable: at deepfm-criteo's
    shape (212 992 ids x 11 columns into 33 800 192 rows, kernel runnable;
    abstract values, nothing runs) the jaxpr is the same text with all
    six retired names set, each to a value that used to change it."""
    from elasticdl_tpu.ops.pallas_attention import interpret_mode

    n, d, rows = 8192 * 26, 11, 33_800_192

    def traced():
        with interpret_mode():
            text = str(jax.make_jaxpr(
                lambda ids, cf: emb_ops.scatter_add_dense(ids, cf, rows))(
                jax.ShapeDtypeStruct((n,), jnp.int32),
                jax.ShapeDtypeStruct((n, d), jnp.float32)))
        return re.sub(r"0x[0-9a-f]+", "0x", text)

    before = traced()
    assert "pallas_call" in before
    for name, value in RETIRED_VARIABLES.items():
        monkeypatch.setenv(name, value)
    assert traced() == before
