"""Communication-structure regression tests: compile the multi-device hot
paths on the 8-device virtual mesh and assert the COLLECTIVES in the
optimized HLO move only small buffers.

This pins the framework's scaling claims the same way a numerics test pins
correctness: the docstring schedules (ops/embedding.py: "all_to_all ids →
gather of the owned ids → all_to_all rows" on a data-only mesh, "all_gather
ids → local gather → psum_scatter" elsewhere; ops/attention.py ring: "KV
blocks rotate via ppermute") are only worth anything if a refactor can't silently regress
into a table-sized all-reduce or a full-sequence all-gather — on a real
pod that is the difference between ICI-bound scaling and not scaling.
The reference's analog constraint: PS traffic was per-touched-row pulls and
sparse grad pushes (SURVEY §2.6), never whole-table transfers.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.ops import embedding as emb
from elasticdl_tpu.parallel.mesh import build_mesh

# HLO instruction NAMES use underscores (%all_gather.6); OPCODES use
# hyphens followed by an open paren (` all-gather(`), so requiring the
# hyphenated token + `(` cannot match an operand reference, and the
# -start/-done async forms (tuple-shaped outputs) are covered too.
_OPCODE_RE = re.compile(
    r"\s((?:all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|all-to-all)(?:-start|-done)?)\("
)
_SHAPE_RE = re.compile(r"[a-z]+\d+\[([\d,]*)\]")


def collective_sizes(hlo_text):
    """[(op, elements)] for every collective in the compiled HLO, measured
    by the LARGEST buffer in the collective's output (async -start ops have
    tuple outputs — the in-flight destination buffer must count, or an
    async table-sized transfer would go unmeasured)."""
    out = []
    for line in hlo_text.splitlines():
        m = _OPCODE_RE.search(line)
        if not m:
            continue
        sizes = [
            int(np.prod([int(d) for d in dims.split(",")])) if dims else 1
            for dims in _SHAPE_RE.findall(line[:m.start()])
        ]
        if sizes:
            out.append((m.group(1), max(sizes)))
    return out


def test_manual_embedding_backward_moves_no_table_sized_buffers(mesh8):
    """fwd+bwd of the manual shard_map lookup on a data=4 x model=2 mesh:
    every collective must be batch-activation-sized (~B*L*D) or smaller —
    NEVER table-sized. A naive schedule (replicated table grad all-reduced
    over data shards) moves V*D per step and caps scaling at the vocab."""
    mesh = build_mesh({"data": 4, "model": 2}, list(mesh8.devices.flat))
    V, D, B, L = emb.padded_vocab(4096), 16, 32, 8
    table = jnp.asarray(np.random.RandomState(0).randn(V, D).astype(np.float32))
    ids = jnp.asarray(
        np.random.RandomState(1).randint(0, V, (B, L)).astype(np.int32))

    from jax.sharding import NamedSharding, PartitionSpec as P

    with jax.set_mesh(mesh):
        table_s = jax.device_put(
            table, NamedSharding(mesh, P(("data", "model"), None)))
        ids_s = jax.device_put(ids, NamedSharding(mesh, P("data", None)))
        f = jax.jit(jax.grad(
            lambda t, i: jnp.sum(emb.embedding_lookup(t, i, mode="manual") ** 2)
        ))
        txt = f.lower(table_s, ids_s).compile().as_text()

    sizes = collective_sizes(txt)
    assert sizes, "expected collectives in the sharded lookup/backward"
    biggest = max(n for _, n in sizes)
    activation_elems = B * L * D
    table_elems = V * D
    # every collective <= the full activation block, far under the table
    assert biggest <= activation_elems, (biggest, sizes)
    assert biggest * 8 <= table_elems, (biggest, table_elems, sizes)
    # schedule sanity: the tiny ids all-gather is present
    assert any(op.startswith("all-gather") for op, _ in sizes), sizes


def test_routed_embedding_exchanges_only_owned_ids_and_rows(mesh8, monkeypatch):
    """fwd+bwd of the manual lookup on a data-only mesh (data=4), at a
    table and a batch past the sorted routes' gates (abstract values: the
    program is compiled, nothing runs): ids and rows cross the mesh
    all-to-all in buckets of `cap` — every collective is <= n_shards * cap
    * D elements, UNDER the (B, L, D) block the gathered schedule reduces.
    A shard's table gathers fetch a buffer of DISTINCT ids of its n_shards
    * cap slots (`emb/fwd/gather`, one a buffer size); all of the slots'
    rows come from the table only inside `emb/fwd/overflow`, and the global batch's B * L
    only inside the routed schedule's own overflow branch
    (`emb/route/overflow`). The stream of a shard's slots is sorted ONCE,
    stably with its positions: the forward's sort is the backward's. The
    fence on the shard's gradient (`_fence_cotangent`, PR 61) is free: the
    same collectives and no table-sized `copy` more than without it."""
    n_shards = 4
    mesh = build_mesh({"data": n_shards}, list(mesh8.devices.flat)[:n_shards])
    V, D, B, L = n_shards * 33 * 8192, 16, 256, 64
    cap = emb.route_cap(B // n_shards * L, n_shards)
    slots = n_shards * cap
    assert slots < B * L
    assert emb.backward_route(slots, V // n_shards, False) == "tiled"
    distinct, distinct_all = emb.distinct_caps(slots), emb.distinct_caps(B * L)

    from jax.sharding import NamedSharding, PartitionSpec as P

    with jax.set_mesh(mesh):
        table_s = jax.ShapeDtypeStruct(
            (V, D), jnp.float32,
            sharding=NamedSharding(mesh, P(("data",), None)))
        ids_s = jax.ShapeDtypeStruct(
            (B, L), jnp.int32, sharding=NamedSharding(mesh, P("data", None)))
        def compiled_text():
            # a new function each call: `jit` keeps a trace, and its fence
            return jax.jit(jax.grad(lambda t, i: jnp.sum(
                emb.embedding_lookup(t, i, mode="manual") ** 2
            ))).lower(table_s, ids_s).compile().as_text()

        txt = compiled_text()
        monkeypatch.setattr(emb, "_fence_cotangent", lambda t: t)
        unfenced = compiled_text()

    sizes = collective_sizes(txt)
    assert any(op.startswith("all-to-all") for op, _ in sizes), sizes
    assert sizes == collective_sizes(unfenced)
    shard_copy = "= f32[%d,%d]{1,0} copy(" % (V // n_shards, D)
    assert txt.count(shard_copy) == unfenced.count(shard_copy)
    # outside the overflow branch nothing is larger than the rows' exchange
    routed = collective_sizes("\n".join(
        line for line in txt.splitlines() if "emb/route/overflow" not in line))
    assert max(n for _, n in routed) <= slots * D, routed
    assert slots * D < B * L * D
    assert max(n for _, n in sizes) <= B * L * D, sizes

    # the forward's gathers, by how many rows they fetch: out of a shard's
    # table (`gather_rows`' two table scopes) and out of the buffer
    fetched, expanded = {}, {}
    for line in txt.splitlines():
        m = re.search(r"= f32\[([\d,]+)\]\S* gather\(", line)
        if not m:
            continue
        rows = int(np.prod([int(x) for x in m.group(1).split(",")])) // D
        if "emb/fwd/gather" in line or "emb/fwd/overflow" in line:
            fetched.setdefault(rows, []).append(line)
        elif "emb/fwd/expand" in line:
            expanded.setdefault(rows, []).append(line)
    assert sorted(expanded) == [slots, B * L], sorted(expanded)
    assert sorted(fetched) == sorted(
        [*distinct, *distinct_all, slots, B * L]), sorted(fetched)
    for rows in (*distinct, *distinct_all):     # each distinct id once
        assert all("emb/fwd/gather" in l and "emb/fwd/overflow" not in l
                   for l in fetched[rows])
    for rows in (slots, B * L):                 # every slot: overflow only
        assert all("emb/fwd/overflow" in l for l in fetched[rows])
    for rows in (*distinct, slots):
        assert all("emb/route/gather" in l for l in fetched[rows])
    for rows in (*distinct_all, B * L):
        assert all("emb/route/overflow" in l for l in fetched[rows])

    # ONE stable sort of (ids, positions) a stream: the shared one
    def stable_sorts(n):
        return [l for l in txt.splitlines()
                if " sort(" in l and "is_stable=true" in l
                and "= (s32[%d]{0}, s32[%d]{0}) " % (n, n) in l]

    assert len(stable_sorts(slots)) == 1, stable_sorts(slots)
    assert "emb/route/gather" in stable_sorts(slots)[0]
    assert len(stable_sorts(B * L)) == 1, stable_sorts(B * L)
    assert "emb/route/overflow" in stable_sorts(B * L)[0]


def test_ring_attention_backward_moves_only_kv_blocks(mesh8):
    """fwd+bwd of ring attention on a data=2 x seq=4 mesh: collectives must
    be per-shard KV-block-sized (collective-permute of (B/d, T/s, H, D)),
    never the full-sequence gather that would defeat sequence parallelism."""
    from elasticdl_tpu.ops.attention import sequence_parallel_attention

    mesh = build_mesh({"data": 2, "seq": 4}, list(mesh8.devices.flat))
    B, T, H, D = 4, 64, 2, 8
    r = np.random.RandomState(0)
    q, k, v = (jnp.asarray(r.randn(B, T, H, D).astype(np.float32))
               for _ in range(3))

    from jax.sharding import NamedSharding, PartitionSpec as P

    with jax.set_mesh(mesh):
        sh = NamedSharding(mesh, P("data", "seq", None, None))
        q_s, k_s, v_s = (jax.device_put(x, sh) for x in (q, k, v))
        f = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                sequence_parallel_attention(q, k, v, causal=True,
                                            mode="ring") ** 2)
        ))
        txt = f.lower(q_s, k_s, v_s).compile().as_text()

    sizes = collective_sizes(txt)
    assert any(op.startswith("collective-permute") for op, _ in sizes), sizes
    block_elems = (B // 2) * (T // 4) * H * D   # one device's KV block
    full_seq_elems = (B // 2) * T * H * D       # what a naive gather moves
    biggest = max(n for _, n in sizes)
    # permutes move single blocks; nothing gathers the full sequence
    assert biggest <= 2 * block_elems, (biggest, block_elems, sizes)
    assert biggest < full_seq_elems, (biggest, full_seq_elems, sizes)


def test_grad_accum_adds_no_resharding_collectives(mesh8):
    """grad_accum's STRIDED micro-batch split must keep each device's
    P('data') rows local: the accumulated step may not introduce
    all-to-all / extra gathers over the accum=1 step (a contiguous split
    would put each micro-batch on a subset of devices and force GSPMD to
    reshard the whole batch every step)."""
    import optax

    from elasticdl_tpu.common.model_utils import load_module
    from elasticdl_tpu.parallel.mesh import build_mesh, shard_batch
    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    mesh = build_mesh({"data": 8}, list(mesh8.devices.flat))
    mod, _ = load_module("model_zoo", "census.wide_deep.custom_model")
    spec = ModelSpec(
        model=mod.custom_model(compute_dtype="float32"), loss=mod.loss,
        optimizer=optax.sgd(0.1), dataset_fn=None, eval_metrics_fn=None,
        module_name="census.wide_deep",
    )
    rng = np.random.RandomState(0)
    batch = {
        "features": {
            "dense": rng.rand(32, 5).astype(np.float32),
            "cat": rng.randint(0, 400, (32, 9)).astype(np.int32),
        },
        "labels": rng.randint(0, 2, (32,)).astype(np.int32),
        "mask": np.ones((32,), np.float32),
    }

    def coll_counts(accum):
        t = Trainer(spec, mesh, grad_accum=accum)
        state = t.init_state(batch)
        sb = shard_batch(mesh, batch)
        with jax.set_mesh(mesh):
            txt = jax.jit(t._raw_train_step()).lower(state, sb).compile(
            ).as_text()
        counts = {}
        for op, _ in collective_sizes(txt):
            key = op.replace("-start", "").replace("-done", "")
            counts[key] = counts.get(key, 0) + 1
        return counts

    base = coll_counts(1)
    acc = coll_counts(4)
    # the lookup's own exchange of ids and rows is all-to-all (in the scan's
    # body once, as in the accum=1 step); a reshard of the batch would add
    assert acc.get("all-to-all", 0) == base.get("all-to-all", 0), (acc, base)
    # the split adds no gathers; grad reduction happens ONCE after the scan
    # (not per micro-batch), so nothing should exceed the accum=1 counts
    for op, n in acc.items():
        assert n <= base.get(op, 0), (op, acc, base)
