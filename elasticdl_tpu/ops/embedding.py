"""Mesh-sharded embedding lookup — the HBM replacement for the parameter
server's embedding tables.

Reference parity: the reference stores embedding tables as per-PS-pod hash
maps (elasticdl/pkg/ps/embedding.go), shards rows by `id % ps_num`
(elasticdl/python/worker/ps_client.py), and pays two gRPC round-trips per
minibatch to pull vectors and push sparse gradients
(elasticdl/python/worker/worker.py → pull_embedding_vectors/push_gradients).

Rebuilt TPU-native: the table is ONE `jax.Array` whose rows are sharded
contiguously over every mesh axis. Lookup and gradient scatter-add happen
*inside* the jitted train step, so "pull" and "push" become ICI collectives:

  manual mode (shard_map), two schedules; `owner_route` picks one from the
  ambient mesh at trace time:

    routed — the table's rows are sharded over the data axis alone:
      bucket the LOCAL ids by the shard that owns them  # one sort, no scatter
      all_to_all(ids over data axis)                    # (shards, cap) int32
      local gather of the ids a shard OWNS              # shards x cap rows
      all_to_all(rows back over data axis)              # (shards, cap, D)
      un-bucket to batch order                          # a gather of B/d x L rows
    A bucket holds `cap` ids (`route_cap`: ROUTE_SLACK x the even share,
    static). A step in which any (source, owner) pair overflows it — agreed
    by a pmax over the data axis — takes the gathered schedule under a
    `lax.cond`: nothing is ever dropped, skewed ownership only runs slower.

    gathered — rows sharded over further axes too (ids are replicated over
    those, so an exchange over `data` would not reach every row shard), and
    the routed schedule's overflow branch:
      all_gather(ids over data axis)         # tiny int32 traffic
      local gather of ALL ids, the non-owned ones masked to zero rows
      psum_scatter(partials over data axis)  # returns each device its batch rows
      psum(over model axis)                  # combine row-shard contributions

  backward is the exact transpose (autodiff through shard_map): the cotangent
  rows go back the way the rows came — into the buckets by a gather, never a
  scatter-add of a row at a time — and `gather_rows`' backward sums them into
  the row shard.

  auto mode: `jnp.take` on the sharded table; XLA's SPMD partitioner inserts
  an equivalent collective schedule. Kept as the fallback/baseline; `manual`
  makes the schedule explicit and predictable.

Lazy row materialization (reference: EmbeddingTable lazy-init on first pull)
is replaced by full-table initialization at state-creation time, shard-wise on
each device — XLA wants static shapes, and hashed/mod vocab (see
preprocessing.hashing) bounds the table like the reference's Hashing layer.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common.constants import MeshAxis
from elasticdl_tpu.common.log_utils import default_logger

logger = default_logger(__name__)


@jax.custom_vjp
def gather_rows(table: jax.Array, ids: jax.Array) -> jax.Array:
    """`table[ids]`, fetched a distinct id at a time, with a backward built
    around the TPU's scatter-add.

    Forward: the rows `jnp.take(table, ids, axis=0)` gives, to the bit.
    A row out of a large table in HBM costs the same whatever its width
    and however often the step has fetched it already, and under skewed
    ids most of a step's ids are repeats (PERF.md §5). So where the
    backward sorts the stream anyway (`backward_route` gives `kernel` or
    `tiled`; a short stream or a small table is one `jnp.take`), that
    sort is made HERE and handed to the backward as the residual; the
    runs of the sorted ids are the distinct ids; they are fetched from the
    table (`emb/fwd/gather`) into a buffer small enough for fast memory —
    the least of `distinct_caps(n)`, an eighth or a quarter of the
    stream, that holds them — and the batch's rows are a second gather
    out of that buffer (`emb/fwd/expand`). A step whose distinct ids fit
    neither — uniform ids — takes the one `jnp.take` instead
    (`emb/fwd/overflow`, the last branch of the same `lax.switch`):
    nothing is dropped or approximated, such a step only runs as it did.

    Backward: the dense (rows, D) sum of the cotangent rows at their ids,
    by the route `backward_route` picks from what the code can see — the
    number of ids, the table's rows, and whether the placement kernel can
    run (a TPU, or interpret mode in the CPU tests). No environment
    variable is read on the way.

    - `kernel`: the forward's stable sort of (ids, positions), then the
      Mosaic placement kernel (ops/pallas_scatter.py) one-hot
      matmuls each output block's window of the sorted stream on the MXU.
      A `lax.cond` on the fullest window guards it: a stream whose
      duplicates overflow a window is first compacted to per-distinct-id
      sums (`dedupe_then_place`; under the benchmark's Zipf ids every step
      goes this way), and one whose DISTINCT ids still overflow takes a
      flat sorted scatter.
    - `tiled`: the same sorted stream, scanned over vocab tiles of
      `TILE_ROWS` rows whose scatters each stay inside fast memory
      (`_tiled_table_grad`); a `lax.cond` falls back to the flat scatter
      when a window overflows. Where the kernel cannot run, or its window
      would be too wide.
    - `flat`: XLA's scatter-add of the unsorted stream — what `jnp.take`'s
      own VJP does. Small streams and small tables.

    Numerics: the kernel's MXU runs bfloat16, so it places a two-term
    (hi + lo) bf16 split of each float32 value and accumulates in
    float32: about 4e-6 relative to a float32 accumulation (the
    benchmark's `correct` holds it; `chip_smoke.py` phase B measures it
    against `jnp.take`'s VJP). `dedupe_then_place`'s sums, `tiled` and
    `flat` add in float32, exactly.

    What each piece costs on the chip, per benchmark cell: PERF.md §5; why
    it is built this way: PERF.md §6.
    """
    return _lookup(table, ids)[0]


def _gather_rows_fwd(table, ids):
    out, sorted_ids = _lookup(table, ids)
    # the flat backward scatters the ids as they came; the sorted routes
    # read the forward's sort and nothing else of the ids
    return out, (
        ids if sorted_ids is None else None, sorted_ids,
        jnp.empty((0,), table.dtype), table.shape[0],
    )


# The tiled backward's tile: TILE_ROWS x D x 4B is one scatter's output and
# must stay inside the fast-scatter zone (<= ~16 MB on v5e); 128k rows x 16
# floats = 8 MB leaves headroom for wider embedding dims.
TILE_ROWS = 128 * 1024
# Windows (the tiled path's and the kernel's) are sized at slack x the
# uniform expectation (hashed vocabs make the per-tile population
# near-uniform; uniform max over ~20 tiles sits ~4 sigma = ~4% above the
# mean, so 1.3x is comfortable); the cond fallbacks keep skewed id
# distributions exact. The tiled path's cost is per window SLOT, so its
# window is aligned to 256 rows, not rounded to a power of two — pow2
# rounding nearly doubled the slot count.
WINDOW_SLACK = 1.3
# The widest window the kernel route takes: w scales as slack*n*bs/rows,
# and a small vocab under a huge batch (just past the 2*bs gate) would
# demand a VMEM window far beyond the kernel's ~4 MB budget — those shapes
# take the tiled route instead of failing Mosaic allocation.
KERNEL_MAX_WINDOW = 16384
# Below this many ids the flat scatter is already in (or near) the fast
# zone and sorting and windowing only add overhead.
SORTED_MIN_IDS = 4096


def backward_route(n: int, num_rows: int, kernel_runnable: bool) -> str:
    """Which backward `n` ids into `num_rows` rows take — "kernel", "tiled"
    or "flat" (see `gather_rows`): a pure function of the shapes and of
    whether the placement kernel can run here."""
    from elasticdl_tpu.ops import pallas_scatter

    bs = pallas_scatter.BLOCK_ROWS
    est_w = WINDOW_SLACK * n * bs / max(1, num_rows)
    if (kernel_runnable and num_rows >= 2 * bs and n >= SORTED_MIN_IDS
            and est_w <= KERNEL_MAX_WINDOW):
        return "kernel"
    route = ("tiled" if num_rows > 2 * TILE_ROWS and n >= SORTED_MIN_IDS
             else "flat")
    # trace-time, once per compiled program: which route this shape took
    logger.info(
        "embedding backward (%d ids into %d rows) stays off the Pallas "
        "placement kernel (needs a TPU or interpret mode: %s; rows >= "
        "%d; ids >= %d; window estimate %.0f <= %d) and takes the "
        "XLA %s path", n, num_rows, kernel_runnable, 2 * bs,
        SORTED_MIN_IDS, est_w, KERNEL_MAX_WINDOW, route)
    return route


# The deduped lookup's buffer holds one of these shares of a stream's ids,
# in whole 512s: the least that holds the step's distinct ids. The cells'
# steps hold 10.4% (xDeepFM), 18.8% (deepfm) and about 12% (a shard of
# four) distinct ids. A smaller buffer is cheaper twice over — the table
# is asked for fewer rows (an empty slot costs what a row does) and the
# expansion reads a buffer that sits better in fast memory: at xDeepFM's
# shape an eighth takes 2.8 + 3.9 ms where a quarter takes 9.9 + 8.9 and
# a half 20.9 + 8.9, against 29.4 for the plain gather (PERF.md §6, PR
# 60). A stream with more distinct ids than the last share holds takes
# the plain gather.
DISTINCT_SHARES = (0.125, 0.25)


def distinct_caps(n: int) -> Tuple[int, ...]:
    """The buffers the deduped lookup of `n` ids may take, in rows,
    ascending. Static."""
    return tuple(-(-int(share * n) // 512) * 512 for share in DISTINCT_SHARES)


def _sort_ids(flat):
    """`(sf, order)`: the ids ascending and their positions in `flat`,
    duplicates in batch order — ONE stable sort of (ids, positions).

    `argsort` IS that sort with its sorted ids thrown away; keeping them
    saves the gather `flat[order]`, which cost more than the row gather
    beside it (7.1 ns an id against 1.8-6.2 ns a row: 10.25 and 8.88 ms
    of xDeepFM's step at 1 437 696 ids, my chip runs, PR 26)."""
    return jax.lax.sort(
        (flat, jnp.arange(flat.shape[0], dtype=jnp.int32)),
        dimension=0, is_stable=True, num_keys=1)


def _sorted_runs(sf_sorted):
    """The runs of a SORTED id vector: `(seg, uids)`, both n long — the
    index of slot i's run (compact: seg[0] = 0, steps of 0 or 1), and in
    slot j the j-th distinct id, the slots after the last at int32max
    where the stream's pad already is.

    The j-th distinct id of a sorted vector is its j-th run start, so the
    run starts, everything else sent to int32max, sorted once more (keys
    only) ARE the compact ascending ids. No scatter over the stream: a
    scatter-max (`segment_max`) costs 8.7 ns an id, 12.6 ms of xDeepFM's
    step, where this sort takes 0.8 (PERF.md §6, PR 28)."""
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sf_sorted[1:] != sf_sorted[:-1]])
    seg = jnp.cumsum(is_start) - 1                     # compact, sorted
    uids = jax.lax.sort(
        jnp.where(is_start, sf_sorted, jnp.iinfo(sf_sorted.dtype).max),
        is_stable=False)
    return seg, uids


def _lookup(table, ids):
    """`gather_rows`' forward: `(table[ids], sorted_ids)`, where
    `sorted_ids` is `_sort_ids`' pair if the stream was sorted on the way
    (the deduped lookup) and None if it was not (one `jnp.take`)."""
    from elasticdl_tpu.ops import pallas_scatter

    n, num_rows = ids.size, table.shape[0]
    if backward_route(n, num_rows, pallas_scatter.runnable()) == "flat":
        with jax.named_scope("emb/fwd/gather"):
            return jnp.take(table, ids, axis=0), None
    caps = distinct_caps(n)
    # trace-time, once per compiled program: which shapes dedupe
    logger.info(
        "embedding lookup (%d ids out of %d rows) fetches each distinct id "
        "once: the least of %s rows from the table that holds them, "
        "expanded to the %d; a step with more distinct ids takes the plain "
        "gather", n, num_rows, caps, n)
    # int32, as the backward's stream: see `_gather_rows_bwd`
    flat = ids.reshape(-1).astype(jnp.int32)
    with jax.named_scope("emb/fwd/sort"):
        sf, order = _sort_ids(flat)
        runs = 1 + jnp.sum(sf[1:] != sf[:-1], dtype=jnp.int32)

    def deduped(cap):
        def branch(table, flat, sf, order):
            del flat
            with jax.named_scope("emb/fwd/sort"):
                seg, uids = _sorted_runs(sf)
            with jax.named_scope("emb/fwd/gather"):
                rows_u = jnp.take(table, uids[:cap], axis=0)
            with jax.named_scope("emb/fwd/expand"):
                # the run of each POSITION: a second sort, not a scatter
                # (8 ns an id, PERF.md §6, PR 28)
                _, seg_by_position = jax.lax.sort(
                    (order, seg), is_stable=False, num_keys=1)
                return rows_u.at[seg_by_position].get(
                    mode="promise_in_bounds")

        return branch

    def overflow(table, flat, sf, order):
        del sf, order
        with jax.named_scope("emb/fwd/overflow"):
            return jnp.take(table, flat, axis=0)

    # straight-line branches under one switch, no collective in any: under
    # `shard_map` each shard takes its own
    too_small = jnp.sum(runs > jnp.asarray(caps, jnp.int32))
    out = jax.lax.switch(
        too_small, [deduped(cap) for cap in caps] + [overflow],
        table, flat, sf, order)
    return out.reshape(*ids.shape, table.shape[1]), (sf, order)


def _tiled_table_grad(cf, sf, num_rows):
    """Dense (num_rows, D) gradient from SORTED contributions, every
    scatter confined to the fast zone.

    cf: (N, D) f32 gradient rows already in sorted-id order; sf: (N,)
    sorted int32 ids. Scans vocab tiles of TILE_ROWS rows; tile t
    dynamic-slices a fixed W-row window of (cf, sf) starting at its
    searchsorted edge — contiguous reads, no row gathers — and
    scatter-adds into a TILE_ROWS-row zero tile (mode='drop' masks the
    window tail that belongs to later tiles), then lays tiles down with
    dynamic_update_slice. W covers the max tile population for
    near-uniform (hashed) ids; `lax.cond` falls back to one flat scatter
    when the data is skewed enough to overflow a window."""
    n, d = cf.shape
    tile_rows = TILE_ROWS
    nt = -(-num_rows // tile_rows)
    # Window sizing counts ALL n contributions, including the manual shard
    # path's non-owned sentinels (they sort beyond every real id, so they
    # inflate w but never a tile's population). On an s-shard mesh each
    # shard therefore sweeps ~slack*n window slots when ~n/s would cover
    # its owned rows — the backward stays at single-chip cost rather than
    # scaling down. Known refinement: derive the owned fraction from the
    # static shard count when tracing inside shard_map.
    w = int(min(n, -(-int(max(256.0, WINDOW_SLACK * n / nt)) // 256) * 256))
    vpad = nt * tile_rows
    edges = jnp.searchsorted(
        sf, jnp.arange(0, vpad + 1, tile_rows, dtype=jnp.int32)
    ).astype(jnp.int32)

    def tiled(cf, sf):
        # Pad the sorted stream by one window so a tile's slice NEVER
        # needs a clamped start: window t is then always [monotone
        # in-range ids for tile t][ids of later tiles / pad, all of which
        # map OUT of range high] — the exact shape for which the TPU's
        # drop+sorted scatter lowering is both correct and fast. The
        # design is pinned by on-TPU evidence (round-5 pt2; CPU ignores
        # the flag so only chip numerics can police it):
        #   - clamped starts put invalid slots BEFORE valid ids and the
        #     sorted lowering silently dropped ~27k rows (13%!);
        #   - dropping `indices_are_sorted` instead was exact but 1.6x
        #     slower (31 vs 19 ms) — the fast path span-searches the
        #     sorted window and skips the OOB tail;
        #   - with padding, no masks are needed at all: stray window
        #     slots belong to later tiles, so their tile-local index is
        #     >= tile_rows and mode='drop' discards them by construction.
        # pad ids with int32 max, not vpad: callers may legally pass ids
        # beyond vpad (the manual shard path's non-owned sentinels are
        # 2x the shard size), and a pad value smaller than a real id
        # would make the window tail non-monotone under the sorted
        # promise — the silent-drop trap again
        sf_pad = jnp.concatenate(
            [sf, jnp.full((w,), jnp.iinfo(jnp.int32).max, sf.dtype)])
        cf_pad = jnp.concatenate(
            [cf, jnp.zeros((w, d), cf.dtype)])

        def body(acc, t):
            c_w = jax.lax.dynamic_slice(cf_pad, (edges[t], 0), (w, d))
            s_w = jax.lax.dynamic_slice(sf_pad, (edges[t],), (w,))
            local = s_w - t * tile_rows     # monotone; >= tile_rows drops
            tile = jnp.zeros((tile_rows, d), jnp.float32).at[local].add(
                c_w, mode="drop", indices_are_sorted=True)
            return jax.lax.dynamic_update_slice(
                acc, tile, (t * tile_rows, 0)), None

        # seed the carry from the cotangent so it carries the same
        # varying-manual-axes type as the body's output when this runs
        # inside shard_map (the manual lookup schedule) — a plain
        # jnp.zeros carry is 'unvarying' there and scan rejects the
        # mismatch; the broadcast folds away in XLA
        acc = jnp.zeros((vpad, d), jnp.float32) + cf[:1, :1] * 0.0
        acc, _ = jax.lax.scan(
            body, acc, jnp.arange(nt, dtype=jnp.int32))
        return acc[:num_rows]

    def flat(cf, sf):
        return jnp.zeros((num_rows, d), jnp.float32).at[sf].add(
            cf, mode="drop", indices_are_sorted=True)

    max_pop = jnp.max(edges[1:] - edges[:-1])
    return jax.lax.cond(max_pop <= w, tiled, flat, cf, sf)


def _compact_sorted_duplicates(cf_sorted, sf_sorted):
    """Per-distinct-id sums over a SORTED contribution stream (both outputs
    are n rows, n = stream length). Returns (sums (n, d), uids (n,)) where
    slot j holds the j-th distinct id and its rows' total; the slots after
    the last distinct id hold zero sums at uid = int32max, where the
    stream's pad already is.

    The distinct ids are `_sorted_runs`'."""
    seg, uids = _sorted_runs(sf_sorted)
    return _run_sums(cf_sorted, seg), uids


# What one row scatter's output may take of the v5e's fast memory. The
# compiler lays a small scatter's output out a row to a 128-lane line
# (512 B a row up to D = 128) and keeps it there: 212 992 rows (109 MB)
# scatter at 8.2 ns a row. 239 616 rows (123 MB) are sent to HBM, and from
# 359 424 rows on (REHEARSAL: compiled for a described v5e) the output is
# laid out N-minor, a row in D separate lines: 44.6 ns a row at 851 968
# rows, 64.2 at 1 437 696 (ledger, PR 25: `fusion.13` 38.0 ms of the
# four-chip step, `fusion.6` 92.3 ms of xDeepFM's).
FAST_SCATTER_BYTES = 110 << 20


def _run_sums(cf_sorted, seg):
    """`segment_sum(cf_sorted, seg, num_segments=n)` for the COMPACT sorted
    segment ids `_compact_sorted_duplicates` makes (seg[0] = 0, steps of 0
    or 1), with every scatter's output inside the fast zone.

    A stream of up to `FAST_SCATTER_BYTES` is one segment_sum. A longer
    one is scanned in equal chunks: chunk c's rows belong to segments
    [seg[c*t], seg[c*t] + t), a contiguous range of the output no longer
    than the chunk, so the chunk scatter-adds into that slice of the
    output (taken out and laid back by dynamic slice: two passes of
    bandwidth a chunk) at the small scatter's rate. A run of duplicates
    that straddles a chunk edge goes on adding where the chunk before
    left its sum, in stream order."""
    n, d = cf_sorted.shape
    k = -(-n * 512 * -(-d // 128) // FAST_SCATTER_BYTES)
    if k == 1:
        return jax.ops.segment_sum(
            cf_sorted, seg, num_segments=n, indices_are_sorted=True)
    t = -(-n // (8 * k)) * 8                           # rows a chunk
    # pad rows: zeros at a segment beyond every chunk's range, dropped
    seg = jnp.pad(seg, (0, k * t - n),
                  constant_values=jnp.iinfo(seg.dtype).max)
    cf_sorted = jnp.pad(cf_sorted, ((0, k * t - n), (0, 0)))

    def body(acc, chunk):
        rows, seg_c = chunk
        first = seg_c[0]
        part = jax.lax.dynamic_slice(acc, (first, 0), (t, d))
        part = part.at[seg_c - first].add(
            rows, mode="drop", indices_are_sorted=True)
        return jax.lax.dynamic_update_slice(acc, part, (first, 0)), None

    # the carry takes the rows' varying-manual-axes type (inside shard_map
    # a plain zeros carry is 'unvarying' and scan rejects the mismatch)
    acc = jnp.zeros((k * t, d), cf_sorted.dtype) + jnp.where(
        True, 0.0, cf_sorted[:1, :1])
    acc, _ = jax.lax.scan(
        body, acc, (cf_sorted.reshape(k, t, d), seg.reshape(k, t)))
    return acc[:n]


def _sorted_stream(flat, cf, sorted_ids=None):
    """The backward's sorted stream `(cf_sorted, sf)`: the ids in ascending
    order and the cotangent rows in that order, duplicates in batch order.
    `sorted_ids` is `_sort_ids(flat)` where the forward has made it (the
    deduped lookup's residual); a stream that comes without one
    (`scatter_add_dense`) is sorted here.

    Carrying the D columns through the sort as well was measured and not
    kept: it runs within 1 ms a step of this on every table, and a sort
    of D + 2 operands takes the compiler 200-300 s (PERF.md §6)."""
    with jax.named_scope("emb/bwd/sort"):
        sf, order = _sort_ids(flat) if sorted_ids is None else sorted_ids
        return cf[order], sf


def _block_starts(ids, vpad, bs):
    """`jnp.searchsorted(ids, arange(0, vpad + 1, bs))` (side "left") for
    SORTED int32 `ids`: edges[b] = how many ids lie below row b * bs, the
    column where block b begins. Straight-line code, exact on any sorted
    input (duplicates, empty blocks, sentinels beyond `vpad`).

    `searchsorted` itself is a loop of log2(n) dependent steps, each a
    gather of one scalar per block: 1.97 ms on deepfm-criteo's 16.5k
    blocks where this takes 0.11 (PERF.md §6, PR 28). Here the ids are read
    as lines of `line` ids (the last one padded with int32max). All lines
    whose FIRST id is below a query lie below it entirely, except the last
    of them, which holds the edge: count those lines (a compare of every
    query with every line's first id), fetch that one line per query, and
    count inside it. The work is queries x (n / line + line), least at
    line = sqrt(n): the power of two nearest to it, in whole 128-lane
    rows, from the stream's length alone."""
    n = ids.shape[0]
    imax = jnp.iinfo(jnp.int32).max
    queries = jnp.arange(0, vpad + 1, bs, dtype=jnp.int32)
    line = 128 * max(1, 2 ** round(math.log2(math.sqrt(n) / 128)))
    m = -(-n // line)
    lines = jnp.pad(
        ids, (0, m * line - n), constant_values=imax).reshape(m, line)
    below = jnp.sum(lines[:, 0][None, :] < queries[:, None], axis=1,
                    dtype=jnp.int32)
    last = jnp.maximum(below - 1, 0)       # no line below: line 0 counts 0
    inside = jnp.sum(lines[last] < queries[:, None], axis=1,
                     dtype=jnp.int32)
    return last * line + inside


def _kernel_stream(rows, ids, w, vpad, bs):
    """The placement kernel's input layout, made in ONE place: `rows`
    (n, d) in sorted-id order and their sorted int32 `ids` ->
    (`cf_t` (d8, n + w) the rows transposed and zero-padded, `sf_pad`
    (n + w,) the ids padded with int32max, `edges` (vpad / bs + 1,) the
    column where each block of `bs` rows begins).

    The expression is as fragile as it is plain: the kernel's time
    depends on where XLA keeps this operand. Written as one `jnp.pad` of
    the transpose, the same values got an N-minor `{0,1}` layout, the copy
    that repairs it went to HBM, the kernel's window DMAs read from HBM
    and not from fast memory, and `emb_place_ms` went 6.70 -> 14.04
    (-13% on deepfm-criteo.resident; PERF.md §6, PR 26). Compare the AOT
    text (`S(1)` after the operand's layout) before and after any change
    here."""
    n, d = rows.shape
    imax = jnp.iinfo(jnp.int32).max
    sf_pad = jnp.concatenate([ids, jnp.full((w,), imax, ids.dtype)])
    # transpose FIRST, pad on lanes: the (N, D) -> (D, N) relayout of the
    # small sorted stream fuses with the reorder gather (~0.7 ms
    # measured), while transpose-of-concat materialized a separate 2 ms
    # copy
    # depth padded to the Mosaic sublane tile (8): D=17 (deepfm's merged
    # linear column) would otherwise fail the DMA alignment check
    d8 = -(-d // 8) * 8
    cf_t = jnp.concatenate([
        jnp.concatenate([rows.T, jnp.zeros((d8 - d, n), rows.dtype)], axis=0),
        jnp.zeros((d8, w), rows.dtype),
    ], axis=1)
    with jax.named_scope("emb/bwd/edges"):
        edges = _block_starts(ids, vpad, bs)
    return cf_t, sf_pad, edges


def _pallas_table_grad(cf, sf, num_rows):
    """Dense gradient via the MXU one-hot placement kernel
    (ops/pallas_scatter.py) — same windowing contract as the tiled path
    (sorted stream, searchsorted block starts, lax.cond flat fallback on
    window overflow), but the per-block placement is dense matmul instead
    of fast-zone scatters."""
    from elasticdl_tpu.ops import pallas_scatter

    n, d = cf.shape
    bs = pallas_scatter.BLOCK_ROWS
    vpad = -(-num_rows // bs) * bs
    w = pallas_scatter.window_cols(n, num_rows, bs, WINDOW_SLACK)
    # what a window holds of its own block: w less the up-to-127 columns
    # its aligned start may lie before the block's first id
    room = w - pallas_scatter.LANES

    def pallas_branch(cf_t, sf_pad, edges):
        from elasticdl_tpu.ops.pallas_attention import kernel_interpret

        with jax.named_scope("emb/bwd/place"):
            out_t = pallas_scatter.place_sorted_grads(
                cf_t, sf_pad[None, :], edges[:-1],
                num_rows=vpad, block_rows=bs, w=w, d_out=d,
                interpret=kernel_interpret(),
            )
            # kernel emits (D, vpad) — rows on lanes, see pallas_scatter —
            # one bandwidth-class transpose restores the param layout
            return out_t[:, :num_rows].T

    def flat(cf_t, sf_pad, edges):
        del edges
        return jnp.zeros((num_rows, d), jnp.float32).at[sf_pad[:n]].add(
            cf_t[:d, :n].T, mode="drop", indices_are_sorted=True)

    def dedupe_then_place(cf_t, sf_pad, edges):
        """Skew middle path (executed only when a window overflows): a
        hot id concentrates its duplicates in ONE tile, but duplicates
        are ADJACENT in the sorted stream — compact them with fast-zone
        segment ops (n-row outputs), then place the per-unique sums with
        the same kernel. Window populations become DISTINCT-id counts,
        which hashing spreads near-uniformly, so real-world head skew
        stays on the MXU path: under the benchmark's Zipf ids EVERY step
        comes this way (PERF.md §5). A final flat fallback remains for
        adversarially CLUSTERED distinct ids."""
        del edges
        with jax.named_scope("emb/bwd/dedupe"):
            sums, uids = _compact_sorted_duplicates(
                cf_t[:d, :n].T, sf_pad[:n])
            # real out-of-range ids (manual-path sentinels; their
            # cotangents are zero) join the empty trailing slots at
            # int32max: sorted with the pad, matching no window, dropped
            # by every placement below
            uids = jnp.where(
                uids >= num_rows, jnp.iinfo(jnp.int32).max, uids)
        cf2_t, sf2, edges2 = _kernel_stream(sums, uids, w, vpad, bs)
        max_pop2 = jnp.max(edges2[1:] - edges2[:-1])
        return jax.lax.cond(
            max_pop2 <= room, pallas_branch, flat, cf2_t, sf2, edges2)

    # Window statistics assume near-uniform ids (hashed vocab); skewed
    # data routes through the dedupe middle path above.
    cf_t, sf_pad, edges = _kernel_stream(cf, sf, w, vpad, bs)
    max_pop = jnp.max(edges[1:] - edges[:-1])
    return jax.lax.cond(
        max_pop <= room, pallas_branch, dedupe_then_place,
        cf_t, sf_pad, edges)


def _gather_rows_bwd(res, ct):
    from elasticdl_tpu.ops import pallas_scatter

    ids, sorted_ids, proto, num_rows = res
    cf = ct.reshape(-1, ct.shape[-1]).astype(jnp.float32)
    n = cf.shape[0]
    if n == 0:  # static: empty batch, zero gradient
        return jnp.zeros((num_rows, ct.shape[-1]), proto.dtype), None
    # int32: the stream's pad and the dedupe path's empty slots are
    # int32max, and the kernel subtracts block bases from the ids; vocab
    # sizes are far below 2^31
    flat = None if ids is None else ids.reshape(-1).astype(jnp.int32)
    route = backward_route(n, num_rows, pallas_scatter.runnable())
    if route == "flat":
        d_table = jnp.zeros((num_rows, cf.shape[1]), jnp.float32).at[
            flat].add(cf, mode="drop")
    else:
        sorted_grad = (
            _pallas_table_grad if route == "kernel" else _tiled_table_grad)
        d_table = sorted_grad(
            *_sorted_stream(flat, cf, sorted_ids), num_rows)
    return d_table.astype(proto.dtype), None


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def scatter_add_dense(
    ids: jax.Array, rows: jax.Array, num_rows: int,
    dtype=jnp.float32,
) -> jax.Array:
    """Dense (num_rows, D) sum of `rows` placed at `ids` — the embedding
    tier's push hot path, by the SAME route as the training backward
    (`backward_route`: the placement kernel with its dedupe middle path,
    the tiled fast-zone scan, or the flat XLA scatter).

    ids: int32 (N,) — out-of-range ids (negative padding sentinels,
    anything >= num_rows) are dropped, contributing nothing. rows: (N, D)
    contribution rows. The duplicates-ADD semantics match a sparse
    gradient push: duplicate ids accumulate. Empty N is a static no-op
    (zeros). This is exactly `gather_rows`'s VJP applied to an explicit
    cotangent, so every kernel-route guarantee (window guards, skew dedupe,
    bf16 split accuracy) documented there applies here unchanged."""
    ids = jnp.asarray(ids, jnp.int32).reshape(-1)
    rows = jnp.asarray(rows)
    rows = rows.reshape(-1, rows.shape[-1])
    # same routing as embedding_lookup: out-of-range ids (padding
    # sentinels) go to a LARGE value so the sorted paths never pile them
    # into tile 0's window (see the lookup's oob note)
    oob = jnp.iinfo(jnp.int32).max // 2
    in_range = (ids >= 0) & (ids < num_rows)
    safe_ids = jnp.where(in_range, ids, oob)
    rows = jnp.where(in_range[:, None], rows, 0)
    # no forward ran here: the stream is sorted by the backward itself
    d_table, _ = _gather_rows_bwd(
        (safe_ids, None, jnp.empty((0,), dtype), num_rows), rows
    )
    return d_table



# Table rows are padded to a multiple of this so every device of any mesh up
# to this many chips gets an equal shard (shard_map needs even shards).
VOCAB_ALIGN = 256
# Large tables align to 8192 instead: the Pallas placement kernel emits
# whole row-blocks, and a vocab that isn't block-aligned costs a 178 MB
# epilogue slice-copy (~4 ms/step measured) to trim the padding. 8192 is
# a multiple of the kernel's block (pallas_scatter.BLOCK_ROWS, 2048) and
# of every power-of-two block up to itself. Absolute overhead is bounded
# by 8191 extra rows (~0.5 MB at D=16).
# NOTE (round-5 geometry change): tables created before this alignment
# existed were padded to 256; their checkpoints restore only into models
# built with the same geometry (pass align=VOCAB_ALIGN explicitly to
# reproduce it). The padded vocab has always been baked into checkpoints —
# this changes which value large-vocab models bake.
PALLAS_VOCAB_MIN = 64 * 1024
PALLAS_VOCAB_ALIGN = 8192


def padded_vocab(vocab_size: int, align: Optional[int] = None) -> int:
    if align is None:
        align = (PALLAS_VOCAB_ALIGN
                 if vocab_size >= PALLAS_VOCAB_MIN else VOCAB_ALIGN)
    return ((vocab_size + align - 1) // align) * align


def geometry_descriptor() -> dict:
    """The vocab-padding rule baked into embedding-table shapes, as data.

    Checkpoints persist padded tables, so the padding rule is part of the
    checkpoint geometry: a model rebuilt under a *different* rule cannot
    restore them (orbax shape mismatch). CheckpointManager records this
    descriptor beside every checkpoint dir and compares it on a failed
    restore, turning the raw shape error into an actionable message
    ("rebuild with vocab_align=256"). `geometry_version` bumps whenever the
    rule changes: v1 = align 256 for every vocab; v2 (round 5) = 8192 for
    vocabs >= 64k.
    """
    return {
        "geometry_version": 2,
        "vocab_align": VOCAB_ALIGN,
        "pallas_vocab_align": PALLAS_VOCAB_ALIGN,
        "pallas_vocab_min": PALLAS_VOCAB_MIN,
    }


def ambient_axes() -> Tuple[str, ...]:
    """Mesh axis names of the ambient `jax.set_mesh` context ('' if none)."""
    mesh = jax.sharding.get_abstract_mesh()
    return tuple(mesh.axis_names)


def table_partition_axes(axes: Optional[Sequence[str]] = None) -> Tuple[str, ...]:
    """Axes that shard embedding rows: every ambient mesh axis, in order."""
    if axes is not None:
        return tuple(axes)
    return ambient_axes()


# The routed lookup's buckets hold this many times a shard's even share of
# a source's ids. Hashed fields over contiguous rows put 1/n of every
# sample's ids on each of n shards, give or take the field a shard boundary
# cuts: 26 fields over 4 shards is 6.5 fields a shard, so 7/26 = 1.08 x the
# share at the fullest. 1.5 leaves that room and still gathers 0.375 of the
# global batch a shard where the gathered schedule gathers all of it; what
# overflows takes the gathered schedule for that step.
ROUTE_SLACK = 1.5


def route_cap(n_local: int, n_shards: int) -> int:
    """Ids a (source, owner) bucket of the routed lookup holds: ROUTE_SLACK x
    the even share of a source's `n_local` ids, in whole 512s. Static."""
    return -(-math.ceil(ROUTE_SLACK * n_local / n_shards) // 512) * 512


def owner_route(n_local: int, n_shards: int,
                other_axes: Sequence[str]) -> str:
    """Which schedule the manual lookup of `n_local` ids a device takes on a
    mesh of `n_shards` row shards, `other_axes` of them not the data axis:
    "auto" (one device: nothing to exchange), "gathered" (rows sharded over
    more than the data axis) or "routed" (module docstring). A pure function
    of what the trace can see."""
    if n_shards == 1:
        # a 1-device mesh has nothing to shard: the shard_map schedule
        # only adds manual-axes bookkeeping around the same local
        # gather/scatter (measured round 5: ~8 ms/step of pure
        # overhead in the DeepFM backward) — route to auto
        return "auto"
    route = "gathered" if other_axes else "routed"
    # trace-time, once per compiled program: which schedule this mesh took
    logger.info(
        "embedding lookup (%d local ids over %d row shards, axes beside "
        "data: %s) takes the %s schedule, cap %s", n_local, n_shards,
        tuple(other_axes), route,
        route_cap(n_local, n_shards) if route == "routed" else "n/a")
    return route


def _owner_plan(flat, rows_per_shard, n_shards, cap):
    """Where each of a device's ids goes in its `(n_shards, cap)` send
    buffer. flat: (n,) int32 global ids, the out-of-range ones beyond every
    shard. Returns
      sf      (n,)  the ids ascending — so by owner: rows are contiguous;
      order   (n,)  their positions in `flat` (a stable sort);
      starts  (n_shards,) where owner o's run starts in `sf`;
      counts  (n_shards,) how long it is;
      slot    (n,)  position p's slot o * cap + j in the buffer, j its rank
                    in o's run; `n_shards * cap` for an id no shard owns.
    Counting is a compare and a sum and the inverse arrangement a second
    sort (`ops/moe.py`'s idiom): a TPU scatters one element at a time."""
    n = flat.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    sf, order = jax.lax.sort((flat, pos), is_stable=True, num_keys=1)
    bounds = jnp.arange(n_shards + 1, dtype=jnp.int32) * rows_per_shard
    past = bounds[:, None] <= sf[None, :]              # (n_shards + 1, n)
    first = n - jnp.sum(past, axis=1, dtype=jnp.int32)
    starts, counts = first[:-1], first[1:] - first[:-1]
    # slot of the i-th sorted id: i + (o * cap - starts[o]) for its owner o
    shift = jnp.arange(n_shards, dtype=jnp.int32) * cap - starts
    mine = past[:-1] & ~past[1:]                       # (n_shards, n) one-hot
    slot_sorted = jnp.where(
        past[-1], n_shards * cap,
        pos + jnp.sum(jnp.where(mine, shift[:, None], 0), axis=0))
    _, slot = jax.lax.sort((order, slot_sorted), is_stable=False, num_keys=1)
    return sf, order, starts, counts, slot


def _owner_buckets(x_sorted, starts, counts, cap, fill):
    """x_sorted (n, ...) in `_owner_plan`'s order -> (n_shards, cap, ...):
    bucket o holds owner o's run, then `fill`. Contiguous slices of the
    sorted rows, no gather."""
    tail = (1,) * (x_sorted.ndim - 1)
    x = jnp.concatenate([
        x_sorted, jnp.full((cap,) + x_sorted.shape[1:], fill, x_sorted.dtype)])
    live = jnp.arange(cap, dtype=jnp.int32)[None, :] < counts[:, None]
    return jnp.stack([
        jnp.where(live[o].reshape((cap,) + tail),
                  jax.lax.dynamic_slice_in_dim(x, starts[o], cap), fill)
        for o in range(starts.shape[0])])


@jax.custom_vjp
def _unbucket(buf, slot, order, starts, counts):
    """buf (n_shards, cap, D), the rows of each owner's bucket -> (n, D) in
    batch order: row p is slot[p] of the buffer, zeros where p has no slot."""
    rows = buf.reshape(-1, buf.shape[-1])
    out = rows.at[jnp.minimum(slot, rows.shape[0] - 1)].get(
        mode="promise_in_bounds")
    return jnp.where((slot < rows.shape[0])[:, None], out, 0)


def _unbucket_fwd(buf, slot, order, starts, counts):
    return _unbucket(buf, slot, order, starts, counts), (
        order, starts, counts, buf.shape[1])


def _unbucket_bwd(res, g):
    # the transpose of a gather is a scatter-add; every position takes one
    # slot of its own, so it is also the gather into the plan's sorted order
    # cut into the owners' runs — which a TPU does at memory speed
    order, starts, counts, cap = res
    g_sorted = g.at[order].get(mode="promise_in_bounds")
    return _owner_buckets(g_sorted, starts, counts, cap, 0), None, None, None, None


_unbucket.defvjp(_unbucket_fwd, _unbucket_bwd)


@jax.custom_vjp
def _fence_cotangent(table_shard):
    """Identity on a table shard; its cotangent passes an
    `optimization_barrier` on the way out of the manual lookup's backward.

    The shard's gradient is the result of the backward's `lax.cond`s (the
    schedule's routed-or-overflow, `gather_rows`' kernel-or-flat). XLA moves
    an elementwise user of a conditional's result INTO its branches: without
    the fence the optimizer's `g * g` (Adam's second moment) ran un-fused at
    the end of each branch, a table-sized plane written there and read back
    by the optimizer's pass as one operand more — 6.7 of the four-chip
    cell's 43.7 ms a step (PERF.md §6, PR 61). Behind the barrier the
    conditional's only user is the barrier, which no pass moves, and the
    square fuses into the optimizer's pass as it does on one chip. The
    barrier compiles to no instruction and no copy
    (`tests/test_kernels_aot_mesh.py` reads the compiled text)."""
    return table_shard


def _fence_cotangent_fwd(table_shard):
    return table_shard, None


def _fence_cotangent_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_fence_cotangent.defvjp(_fence_cotangent_fwd, _fence_cotangent_bwd)


def embedding_lookup(
    table: jax.Array,
    ids: jax.Array,
    mode: str = "manual",
) -> jax.Array:
    """Gather rows of a mesh-sharded `table` for a batch of `ids`.

    table: (V, D) sharded P((all mesh axes), None); ids: int32 (B, ...) sharded
    P(data, None...). Returns (B, ..., D) with ids' batch sharding.
    Out-of-range ids return zero vectors (used for padding sentinels).
    """
    axes = ambient_axes()
    in_range = (ids >= 0) & (ids < table.shape[0])
    # Out-of-range ids (the negative padding sentinels of bag features) go
    # to a LARGE out-of-range value, not row 0: the forward masks them
    # either way (jnp.take clips, then in_range zeroes the vectors and
    # their cotangents), but the tiled backward sorts the raw ids — a
    # row-0 pile of pad slots would overflow tile 0's window and
    # permanently trip the flat-scatter fallback (code-review r5 pt4,
    # same pathology as the manual path's non-owned ids). int32max/2
    # stays beyond every padded vocab and survives the shard path's
    # offset subtraction without wrapping.
    oob = jnp.iinfo(jnp.int32).max // 2
    safe_ids = jnp.where(in_range, ids, oob).astype(jnp.int32)

    if mode not in ("manual", "auto"):
        raise ValueError(f"unknown embedding lookup mode {mode!r}")

    route = "auto"
    if mode == "manual" and axes:
        mesh = jax.sharding.get_abstract_mesh()
        data_ax = MeshAxis.DATA if MeshAxis.DATA in axes else axes[0]
        other_axes = tuple(a for a in axes if a != data_ax)
        n_shards = int(np.prod([mesh.shape[a] for a in axes]))
        n_local = safe_ids.size // mesh.shape[data_ax]
        route = owner_route(n_local, n_shards, other_axes)
        if route != "auto" and table.shape[0] % n_shards:
            # The table's padded vocab is fixed at creation time (and baked
            # into checkpoints), but dynamic world resizing can re-form the
            # mesh with a shard count that doesn't divide it (e.g. 1792 rows
            # over 6 devices). shard_map needs even shards; XLA's auto
            # partitioner does not — fall back to the auto schedule for this
            # (rare) geometry.
            logger.warning(
                "table rows (%d) not divisible by %d shards; using "
                "auto-sharded lookup for this mesh (align the vocab via "
                "padded_vocab for the manual schedule)",
                table.shape[0], n_shards,
            )
            route = "auto"

    if route == "auto":
        out = gather_rows(table, safe_ids)
        return jnp.where(in_range[..., None], out, 0.0)

    ids2d = safe_ids.reshape(safe_ids.shape[0], -1)  # (B, L)
    rows_per_shard = table.shape[0] // n_shards
    # Ids a shard does not own map OUT of its range (not to row 0): the
    # forward clamps/masks them either way, but the backward's tiled
    # scatter sorts the raw ids — a row-0 pile of every non-owned id
    # (up to (n_shards-1)/n_shards of the batch) would overflow tile
    # 0's window and trip the lax.cond flat fallback EVERY step,
    # silently making the sorted routes slower than the flat scatter
    # on exactly the multi-chip manual path (code-review r5 pt3).
    # 2x the shard size specifically: the tiled backward's padded
    # vocab is < 1.5x num_rows (tile_rows < num_rows/2 on that path),
    # so 2x sits beyond the last searchsorted edge and the sentinels
    # count toward NO tile's window population; every route drops
    # out-of-range cotangent rows.
    sentinel = jnp.int32(2 * rows_per_shard)

    def owned_rows(table_shard, global_ids):
        """The rows of `global_ids` this shard owns, zeros for the rest."""
        local = global_ids - jax.lax.axis_index(axes) * rows_per_shard
        owned = (local >= 0) & (local < rows_per_shard)
        return jnp.where(
            owned[..., None],
            gather_rows(table_shard, jnp.where(owned, local, sentinel)), 0.0)

    def gathered(table_shard, ids_local):
        # table_shard: (V/n, D); ids_local: (B/d, L)
        all_ids = jax.lax.all_gather(ids_local, data_ax, tiled=True)  # (B, L)
        out = jax.lax.psum_scatter(
            owned_rows(table_shard, all_ids), data_ax,
            scatter_dimension=0, tiled=True)  # (B/d, L, D)
        if other_axes:
            out = jax.lax.psum(out, other_axes)
        return out

    def routed(table_shard, ids_local, sf, order, starts, counts, slot):
        with jax.named_scope("emb/route/bucket"):
            # an empty slot holds `oob`, which no shard owns
            send = _owner_buckets(sf, starts, counts, cap, oob)
        with jax.named_scope("emb/route/exchange"):
            asked = jax.lax.all_to_all(send, data_ax, 0, 0)  # (n_shards, cap)
        with jax.named_scope("emb/route/gather"):
            rows = owned_rows(table_shard, asked)       # (n_shards, cap, D)
        with jax.named_scope("emb/route/exchange"):
            rows = jax.lax.all_to_all(rows, data_ax, 0, 0)
        with jax.named_scope("emb/route/unbucket"):
            out = _unbucket(rows, slot, order, starts, counts)
        return out.reshape(*ids_local.shape, table_shard.shape[1])

    def overflow(table_shard, ids_local, *plan):
        del plan
        with jax.named_scope("emb/route/overflow"):
            return gathered(table_shard, ids_local)

    def routed_or_overflow(table_shard, ids_local):
        with jax.named_scope("emb/route/bucket"):
            plan = _owner_plan(
                ids_local.reshape(-1), rows_per_shard, n_shards, cap)
            counts = plan[3]
            # every shard takes the same branch: the branches hold
            # collectives
            full = jax.lax.pmax(jnp.max(counts), data_ax) > cap
        return jax.lax.cond(
            full, overflow, routed, table_shard, ids_local, *plan)

    if route == "routed":
        cap = route_cap(n_local, n_shards)
    schedule = routed_or_overflow if route == "routed" else gathered

    def fenced(table_shard, ids_local):
        # whichever schedule runs (and however the two are folded one day):
        # the shard's gradient leaves it behind a barrier
        return schedule(_fence_cotangent(table_shard), ids_local)

    out = jax.shard_map(
        fenced,
        in_specs=(P(axes, None), P(data_ax, None)),
        out_specs=P(data_ax, None, None),
    )(table, ids2d)
    out = out.reshape(*safe_ids.shape, table.shape[1])
    return jnp.where(in_range[..., None], out, 0.0)


def combine(vectors: jax.Array, combiner: Optional[str], ids: jax.Array,
            weights: Optional[jax.Array] = None) -> jax.Array:
    """Bag-combine (B, L, D) lookups over L (reference: the Embedding layer's
    `combiner` for sparse bag inputs). Pad slots are marked by negative ids.

    combiner: None → (B, L, D); 'sum'|'mean'|'sqrtn' → (B, D).
    """
    if combiner is None:
        return vectors
    valid = (ids >= 0).astype(vectors.dtype)
    w = valid if weights is None else weights.astype(vectors.dtype) * valid
    weighted = vectors * w[..., None]
    s = jnp.sum(weighted, axis=-2)
    if combiner == "sum":
        return s
    denom = jnp.sum(w, axis=-1, keepdims=True)
    if combiner == "mean":
        return s / jnp.maximum(denom, 1e-9)
    if combiner == "sqrtn":
        return s / jnp.sqrt(jnp.maximum(denom, 1e-9))
    raise ValueError(f"unknown combiner {combiner!r}")
