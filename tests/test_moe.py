"""Switch-MoE expert parallelism (ops/moe.py + api.layers.MoE): routing
semantics, replicated-vs-expert-sharded parity, training, and the
comm-structure bound (no expert-weight-sized collectives).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.api.layers import MoE
from elasticdl_tpu.ops import moe as moe_ops
from elasticdl_tpu.ops import pallas_gmm
from elasticdl_tpu.parallel.mesh import build_mesh

E, C, H, N = 4, 8, 16, 32


def make_weights(seed=0):
    r = np.random.RandomState(seed)
    return dict(
        wg=jnp.asarray(r.randn(C, E), jnp.float32),
        w1=jnp.asarray(r.randn(E, C, H) * 0.1, jnp.float32),
        b1=jnp.zeros((E, H), jnp.float32),
        w2=jnp.asarray(r.randn(E, H, C) * 0.1, jnp.float32),
        b2=jnp.zeros((E, C), jnp.float32),
    )


def reference_moe(x, w):
    """Per-token loop twin of switch_moe with unlimited capacity."""
    probs = np.asarray(jax.nn.softmax(x @ w["wg"], axis=-1))
    out = np.zeros_like(np.asarray(x))
    for i, tok in enumerate(np.asarray(x)):
        e = int(np.argmax(probs[i]))
        hdn = np.asarray(jax.nn.gelu(tok @ w["w1"][e] + w["b1"][e]))
        out[i] = (hdn @ w["w2"][e] + w["b2"][e]) * probs[i, e]
    return out


def test_switch_moe_matches_per_token_reference():
    w = make_weights()
    x = jnp.asarray(np.random.RandomState(1).randn(N, C), jnp.float32)
    # capacity ample: nothing dropped -> must equal the per-token loop
    out, aux = moe_ops.switch_moe(
        x, w["wg"], w["w1"], w["b1"], w["w2"], w["b2"],
        capacity_factor=float(E))
    np.testing.assert_allclose(np.asarray(out), reference_moe(x, w),
                               rtol=1e-4, atol=1e-5)
    assert float(aux) > 0.0


def test_switch_moe_capacity_drops_overflow_tokens():
    w = make_weights()
    # router forced: positive tokens + positive-only column 0 weights make
    # expert 0's logit strictly dominate for EVERY token
    w["wg"] = jnp.zeros((C, E)).at[:, 0].set(10.0)
    x = jnp.asarray(
        np.abs(np.random.RandomState(2).randn(N, C)) + 0.1, jnp.float32)
    cap = max(1, int(0.25 * N / E))   # 2 slots
    out, _ = moe_ops.switch_moe(
        x, w["wg"], w["w1"], w["b1"], w["w2"], w["b2"],
        capacity_factor=0.25)
    nonzero_rows = np.count_nonzero(
        np.any(np.abs(np.asarray(out)) > 1e-9, axis=-1))
    assert nonzero_rows == cap, (nonzero_rows, cap)   # overflow -> 0 (residual)


def test_moe_layer_parity_replicated_vs_expert_sharded():
    """The SAME init on an expert-sharded mesh and a data-only mesh must
    produce the same output — expert parallelism is a layout, not a
    semantics change."""
    x = jnp.asarray(np.random.RandomState(3).randn(4, 8, C), jnp.float32)
    layer = MoE(num_experts=E, hidden_dim=H)

    def run(mesh):
        with jax.set_mesh(mesh):
            import flax.linen as nn

            boxed = layer.init(jax.random.PRNGKey(0), x)
            # commit the annotated shardings (expert-sharded on the EP
            # mesh, replicated otherwise) so the EP side really shards
            variables = jax.tree_util.tree_map(
                jax.device_put, nn.meta.unbox(boxed),
                nn.get_sharding(boxed, mesh))
            return np.asarray(jax.jit(
                lambda v, x: layer.apply(v, x))(variables, x))

    out_rep = run(build_mesh({"data": 2}, jax.devices()[:2]))
    out_ep = run(build_mesh({"data": 2, "expert": 4}))
    np.testing.assert_allclose(out_ep, out_rep, rtol=1e-4, atol=1e-6)


def test_moe_layer_trains(mesh8):
    """A tiny classifier with an MoE FFN learns on the 8-device mesh (no
    expert axis: replicated experts, same code path the trainer uses)."""
    import flax.linen as nn
    import optax

    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    class MoEModel(nn.Module):
        @nn.compact
        def __call__(self, feats, training=False):
            h = nn.Dense(C)(feats)
            h = MoE(num_experts=E, hidden_dim=H)(h)
            return nn.Dense(1)(h).reshape(-1)

    spec = ModelSpec(
        model=MoEModel(),
        loss=lambda labels, out: optax.sigmoid_binary_cross_entropy(
            out, jnp.asarray(labels, jnp.float32).reshape(-1)),
        optimizer=optax.adam(5e-3),
        dataset_fn=None,
        eval_metrics_fn=None,
    )
    trainer = Trainer(spec, mesh8)

    def batch(seed):
        r = np.random.RandomState(seed)
        feats = r.randn(32, C).astype(np.float32)
        labels = (feats[:, 0] > 0).astype(np.float32)
        return {"features": feats, "labels": labels,
                "mask": np.ones((32,), np.float32)}

    state = trainer.init_state(batch(0))
    losses = []
    for i in range(30):
        state, logs = trainer.train_step(state, batch(i % 5))
        losses.append(float(logs["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


def test_moe_collectives_are_token_sized_not_weight_sized():
    """On a data x expert mesh with the weights COMMITTED to their expert
    sharding and tokens to data sharding, the compiled fwd+bwd must (a)
    actually contain collectives (uncommitted inputs would let GSPMD
    replicate everything, making this vacuous — review-caught) and (b)
    never move the full stacked expert weights: experts stay resident."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tests.test_comm_structure import collective_sizes

    w = make_weights()
    x = jnp.asarray(np.random.RandomState(4).randn(N, C), jnp.float32)
    mesh = build_mesh({"data": 2, "expert": 4})
    def put(k, v):
        # router replicated; every stacked expert leaf sharded over expert
        spec = P() if k == "wg" else P("expert", *([None] * (v.ndim - 1)))
        return jax.device_put(v, NamedSharding(mesh, spec))

    w = {k: put(k, v) for k, v in w.items()}
    x = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    weight_elems = E * C * H          # stacked w1
    with jax.set_mesh(mesh):
        hlo = (
            jax.jit(jax.grad(
                lambda w: jnp.sum(moe_ops.switch_moe(
                    x, w["wg"], w["w1"], w["b1"], w["w2"], w["b2"])[0] ** 2)))
            .lower(w).compile().as_text()
        )
    sizes = collective_sizes(hlo)
    assert sizes, "expected token-movement collectives in the sharded MoE HLO"
    for op, nelem in collective_sizes(hlo):
        assert nelem < weight_elems, (op, nelem, "expert weights crossed the mesh")


def test_aux_loss_weight_enters_training_loss(mesh8):
    """ModelSpec.aux_loss_weight threads sown "losses" into the
    DIFFERENTIATED loss: the same init trained one step with weight w
    reports loss_0 + w * aux (aux read from a mutable apply), and the two
    runs produce different params (the aux actually regularizes)."""
    import flax.linen as nn
    import optax

    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    class M(nn.Module):
        @nn.compact
        def __call__(self, feats, training=False):
            h = nn.Dense(C)(feats)
            h = MoE(num_experts=E, hidden_dim=H)(h)
            return nn.Dense(1)(h).reshape(-1)

    def batch(seed=0):
        r = np.random.RandomState(seed)
        feats = r.randn(32, C).astype(np.float32)
        return {"features": feats,
                "labels": (feats[:, 0] > 0).astype(np.float32),
                "mask": np.ones((32,), np.float32)}

    W = 0.5

    def one_step(weight):
        spec = ModelSpec(
            model=M(),
            loss=lambda l, o: optax.sigmoid_binary_cross_entropy(
                o, jnp.asarray(l, jnp.float32).reshape(-1)),
            optimizer=optax.sgd(0.1),
            dataset_fn=None,
            eval_metrics_fn=None,
            aux_loss_weight=weight,
        )
        t = Trainer(spec, mesh8, seed=0)
        state = t.init_state(batch())
        state, logs = t.train_step(state, batch())
        return state, float(logs["loss"])

    state0, loss0 = one_step(0.0)
    state_w, loss_w = one_step(W)
    aux = float(
        jax.tree_util.tree_leaves(state_w.extra_vars["losses"])[0])
    assert loss_w == pytest.approx(loss0 + W * aux, rel=1e-4), (
        loss_w, loss0, aux)
    # and it changed the update direction (router params differ)
    p0 = np.asarray(
        jax.tree_util.tree_leaves(state0.params)[0])
    pw = np.asarray(
        jax.tree_util.tree_leaves(state_w.params)[0])
    assert not np.allclose(p0, pw)


def test_moe_transformer_lm_trains():
    """moe_experts=4 in the zoo LM: Switch-MoE FFN per block with the
    module-level aux_loss_weight; loss falls on the bigram stream."""
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.data.reader import SyntheticDataReader
    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    cfg = JobConfig(
        model_zoo="model_zoo",
        model_def="transformer.transformer_lm.custom_model",
        model_params={
            "vocab": 64, "num_layers": 2, "dim": 64, "heads": 4,
            "max_len": 64, "seq_parallel": "none", "moe_experts": 4,
            "compute_dtype": "float32",
        },
    )
    spec = ModelSpec.from_config(cfg)
    assert spec.aux_loss_weight == pytest.approx(0.01)
    reader = SyntheticDataReader(kind="lm", num_records=512, vocab=64,
                                 seq_len=32)
    mesh = build_mesh({"data": 2, "expert": 4})
    trainer = Trainer(spec, mesh, seed=0)
    parse = spec.dataset_fn("training", reader.metadata)

    def batch(i, n=8):
        feats, labs = zip(*(parse(r) for r in
                            reader.read_records("s", i * n, (i + 1) * n)))
        return {"features": np.stack(feats), "labels": np.stack(labs),
                "mask": np.ones((n,), np.float32)}

    state = trainer.init_state(batch(0))
    # expert FFNs shard over the expert axis
    w1 = state.params["block_0"]["moe"]["w1"]
    assert "expert" in tuple(w1.sharding.spec), w1.sharding.spec
    losses = []
    for i in range(12):
        state, logs = trainer.train_step(state, batch(i % 8))
        losses.append(float(logs["loss"]))
    assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses


# ------------------------------------------------------------------ #
# the sigmoid router and the held dispatch (Nemotron-H, DeepSeek-V3 style)


def test_sigmoid_topk_route_by_hand():
    logits = jnp.asarray([[2.0, 0.0, -1.0, 1.0], [0.1, 0.2, 0.3, 0.4]], jnp.float32)
    bias = jnp.asarray([0.0, 0.6, 0.0, 0.0], jnp.float32)
    scores, weights, idx = moe_ops.sigmoid_topk_route(logits, bias, 2, 2.5)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits)))
    np.testing.assert_allclose(scores, s, rtol=1e-6)
    # token 0: 0.881, 0.5 + 0.6, 0.269, 0.731 -> experts 1 and 0: the bias
    # selects expert 1 over expert 3 ...
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1]
    # ... and does not weigh: the weights are the SCORES, renormalised, x 2.5
    chosen = s[0, np.asarray(idx[0])]
    np.testing.assert_allclose(weights[0], 2.5 * chosen / chosen.sum(), rtol=1e-6)
    np.testing.assert_allclose(np.sum(np.asarray(weights), axis=-1), 2.5, rtol=1e-6)
    assert sorted(np.asarray(idx[1]).tolist()) == [1, 3]


@pytest.mark.parametrize("eps", [None, 1e-20, 1e-6])
def test_sigmoid_topk_route_s_renormaliser_is_an_argument(eps):
    """`eps` is what the chosen scores' sum is raised by: left out it is the
    1e-20 its callers had — the same weights to the last bit — and 1e-6
    (`lfm2_moe.py`) lowers every weight by that over the sum, no choice."""
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(64, 8)), jnp.float32)
    bias = jnp.asarray(np.random.default_rng(1).normal(size=(8,)) * 0.1, jnp.float32)
    given = {} if eps is None else {"eps": eps}
    scores, weights, idx = moe_ops.sigmoid_topk_route(logits, bias, 3, 1.0, **given)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    total = jnp.sum(chosen, axis=-1, keepdims=True)
    np.testing.assert_array_equal(weights, 1.0 * chosen / (total + (eps or 1e-20)))
    _, plain, plain_idx = moe_ops.sigmoid_topk_route(logits, bias, 3, 1.0)
    np.testing.assert_array_equal(idx, plain_idx)
    if eps == 1e-6:
        rel = np.asarray((plain - weights) / plain)
        assert np.all(rel >= 0) and 1e-7 < rel.max() < 2e-6
    else:
        np.testing.assert_array_equal(weights, plain)


def relu2_loop(x, expert_idx, weights, w_up, w_down, held):
    """Every held expert on every token, a mask on its output."""
    first, count = held
    y = jnp.zeros_like(x)
    for e in range(count):
        out = jnp.square(jax.nn.relu(x @ w_up[e])) @ w_down[e]
        y = y + jnp.sum(jnp.where(expert_idx == first + e, weights, 0.0), axis=1)[:, None] * out
    return y


def gated_silu_loop(x, expert_idx, weights, w_gate, w_up, w_down, held):
    """The three-matrix body, every held expert on every token, masked."""
    first, count = held
    y = jnp.zeros_like(x)
    for e in range(count):
        out = (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
        y = y + jnp.sum(jnp.where(expert_idx == first + e, weights, 0.0), axis=1)[:, None] * out
    return y


def held_routings(n, k, num_experts, held):
    first, count = held
    even = np.stack([(np.arange(n) * k + s) % num_experts for s in range(k)], axis=1)
    on_held = first + even % count                     # every pair on a held expert
    off_held = np.where(even % num_experts < first, even,
                        (first + count + even) % num_experts)
    off_held = np.where((off_held >= first) & (off_held < first + count), 0, off_held)
    return {"even": even, "every_pair_on_a_held_expert": on_held,
            "no_pair_on_a_held_expert": off_held}


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("pass_rows", [0, 40, 47, 48, 96])
@pytest.mark.parametrize("routing", ["even", "every_pair_on_a_held_expert",
                                     "no_pair_on_a_held_expert"])
@pytest.mark.parametrize("body", ["relu2", "gated_silu"])
def test_held_dispatch_matches_loop_over_held_experts(body, routing, pass_rows, direction,
                                                      monkeypatch):
    """16 experts, experts 4-7 held, relu² bodies (two matrices) and gated
    SiLU bodies (three). At 40 rows a pass the 192 pairs that all land on
    held experts take five passes, the last one part full; the even routing
    puts 48 pairs on them, which at 48 rows fill the first pass to its last
    row and run no overflow, and at 47 rows leave ONE live row to one
    overflow pass; with none on a held expert the first pass runs and adds
    nothing — the output and every gradient are exact zeros: nothing is
    dropped. At a row tile of 16 a pass of 48, 96 or all 192 rows is walked
    in chunks of 16 (40 and 47 are no whole tiles: one chunk): the even
    routing's 48 pairs fill the pass of 48 to its last chunk and end exactly
    on the third chunk's boundary in the passes of 96 (of six) and 192 (of
    twelve); all 192 on held experts fill every chunk of every pass."""
    n, c, f, e, k, held = 64, 16, 8, 16, 3, (4, 4)
    monkeypatch.setattr(pallas_gmm, "ROW_TILE", 16)
    assert [moe_ops.held_row_chunk(rows) for rows in (40, 47, 48, 96, 192)] == [
        40, 47, 16, 16, 16]
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(n, c)), jnp.float32)
    up_like = ((held[1], c, f),) * (2 if body == "gated_silu" else 1)
    w = tuple(jnp.asarray(r.normal(size=s) * 0.3, jnp.float32)
              for s in up_like + ((held[1], f, c),))
    by_loop = gated_silu_loop if body == "gated_silu" else relu2_loop
    weights = jnp.asarray(r.uniform(0.1, 1.0, size=(n, k)), jnp.float32)
    idx = jnp.asarray(held_routings(n, k, e, held)[routing], jnp.int32)
    on_held = int(np.sum((np.asarray(idx) >= 4) & (np.asarray(idx) < 8)))
    assert moe_ops.held_pass_rows(n * k, e, held[1]) == n * k
    assert moe_ops.held_pass_rows(49152, 128, 8) == 6144    # twice the even share
    if pass_rows:
        monkeypatch.setattr(moe_ops, "held_pass_rows", lambda pairs, e, count: pass_rows)
    if routing == "no_pair_on_a_held_expert":
        assert on_held == 0
    if routing == "even":
        assert on_held == 48
    if routing == "every_pair_on_a_held_expert":
        assert on_held == n * k
    run = lambda x, weights, *w: moe_ops.dropless_moe(
        x, idx, weights, w, held=held, num_experts=e, compute_dtype=jnp.float32)
    loop = lambda x, weights, *w: by_loop(x, idx, weights, *w, held)
    if direction == "forward":
        np.testing.assert_allclose(run(x, weights, *w), loop(x, weights, *w),
                                   rtol=1e-4, atol=1e-5)
        if not on_held:
            assert not np.any(np.asarray(run(x, weights, *w)))
        return
    probe = jnp.asarray(r.normal(size=(n, c)), jnp.float32)
    every = tuple(range(2 + len(w)))
    got = jax.grad(lambda *a: jnp.sum(probe * run(*a)), argnums=every)(x, weights, *w)
    want = jax.grad(lambda *a: jnp.sum(probe * loop(*a)), argnums=every)(x, weights, *w)
    for g, h in zip(got, want):
        np.testing.assert_allclose(g, h, rtol=1e-4, atol=1e-5)
    if not on_held:
        assert not any(np.any(np.asarray(g)) for g in got)


@pytest.mark.parametrize("pass_rows", [8, 11, 21, 22, 33, 44, 64])
def test_as_many_passes_as_the_held_pairs_fill(pass_rows, monkeypatch):
    """Random routing, passes smaller than the held pairs (8 and 11 rows:
    several passes, group boundaries inside a pass and across passes), one
    row short of them (21: the overflow's loop runs once, for one live row),
    exactly theirs (22: the first pass full, no overflow) and larger (64):
    values and every gradient are the loop's over held experts, under `jit`
    as in a step. At a row tile of 11 the passes of 22, 33 and 44 rows are
    walked in chunks of 11: the 22 held pairs fill both chunks of the first,
    and end exactly on the second chunk's boundary of three and of four."""
    monkeypatch.setattr(moe_ops, "held_pass_rows", lambda pairs, e, count: pass_rows)
    monkeypatch.setattr(pallas_gmm, "ROW_TILE", 11)
    assert [moe_ops.held_row_chunk(rows) for rows in (21, 22, 33, 44, 64)] == [
        21, 11, 11, 11, 64]
    n, c, f, e, k, held = 32, 8, 4, 8, 2, (2, 2)
    r = np.random.default_rng(1)
    x = jnp.asarray(r.normal(size=(n, c)), jnp.float32)
    w = (jnp.asarray(r.normal(size=(2, c, f)), jnp.float32),
         jnp.asarray(r.normal(size=(2, f, c)), jnp.float32))
    weights = jnp.asarray(r.uniform(0.1, 1.0, size=(n, k)), jnp.float32)
    idx = jnp.asarray(r.integers(0, e, size=(n, k)), jnp.int32)
    assert int(np.sum((np.asarray(idx) >= 2) & (np.asarray(idx) < 4))) == 22
    probe = jnp.asarray(r.normal(size=(n, c)), jnp.float32)
    run = lambda x, weights, *w: jnp.sum(probe * moe_ops.dropless_moe(
        x, idx, weights, w, held=held, num_experts=e, compute_dtype=jnp.float32))
    loop = lambda x, weights, *w: jnp.sum(probe * relu2_loop(x, idx, weights, *w, held))
    got = jax.jit(jax.value_and_grad(run, argnums=(0, 1, 2, 3)))(x, weights, *w)
    want = jax.value_and_grad(loop, argnums=(0, 1, 2, 3))(x, weights, *w)
    for g, h in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, h, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("rows, chunk", [
    (65536, 4096), (32768, 2048), (8192, 512), (6144, 512), (4096, 256), (512, 256),
    (12800, 1280), (8704, 4352), (192, 192), (40, 40)])
def test_a_chunk_is_whole_row_tiles_and_at_most_sixteen_make_a_pass(rows, chunk):
    """`held_row_chunk` from the shapes alone: a sixteenth of the pass at the
    six cells' passes (65 536 rows to 4096; 6144 rows are 24 tiles: twelve
    chunks of two), the most equal parts under sixteen that the pass's row
    tiles divide into elsewhere (50 tiles: ten; 34: two), and the whole pass
    where it is one tile or no whole number of tiles. `held_row_chunks` counts
    the chunks that hold a held pair over the passes, as the pass walks them."""
    tm = pallas_gmm.row_tile(rows)
    assert moe_ops.held_row_chunk(rows) == chunk
    assert rows % chunk == 0 and rows // chunk <= 16 and (chunk % tm == 0 or chunk == rows)
    pairs, e, count = 4 * rows, 8, 1                # held_pass_rows: twice an eighth
    if moe_ops.held_pass_rows(pairs, e, count) != rows:
        return
    for on_held, want in [(0, 0), (1, 1), (chunk, 1), (chunk + 1, 2), (rows, rows // chunk),
                          (rows + 1, rows // chunk + 1), (pairs, 4 * (rows // chunk))]:
        assert int(moe_ops.held_row_chunks(jnp.int32(on_held), pairs, e, count)) == want


@pytest.mark.parametrize("scatter", ["in_chunks", "whole"])
@pytest.mark.parametrize("live", [0, 1, 7, 8, 9, 32])
@pytest.mark.parametrize("body", ["relu2", "gated_silu"])
def test_a_pass_s_weighted_add_and_its_pull_back_walk_the_live_chunks_alone(
        body, live, scatter, monkeypatch):
    """`_add_pass_rows` behind a gather and an expert body, against the plain
    `at[].add` it replaces: the value and the cotangents of the tokens, the
    weights and every matrix, for a pass of 32 rows in chunks of 8 with no
    live row, one, a chunk less one, a chunk, a chunk and one, and all. What a
    chunk past the live ones holds is poison: its `ys` and weights NaN, its
    tokens out of range (every other one in range, so that the NaN would
    land) — a visit to a dead chunk, forward or in the pull-back, fails the
    comparison. Chunks larger than `SCATTER_CHUNK_ROWS` take the scatter-add
    in one call over all rows ("whole": the rows past the live ones then hold
    what a pass gives them, zeros and tokens in range) and still pull back in
    chunks."""
    rows, chunk, n, c, f = 32, 8, 16, 8, 4
    if scatter == "whole":
        monkeypatch.setattr(moe_ops, "SCATTER_CHUNK_ROWS", 4)
    r = np.random.default_rng(live)
    xd = jnp.asarray(r.normal(size=(n, c)), jnp.float32)
    y0 = jnp.asarray(r.normal(size=(n, c)), jnp.float32)
    probe = jnp.asarray(r.normal(size=(n, c)), jnp.float32)
    tokens = jnp.asarray(r.integers(0, n, size=rows), jnp.int32)
    weights = jnp.asarray(r.uniform(0.1, 1.0, size=rows), jnp.float32)
    experts = tuple(jnp.asarray(r.normal(size=s) * 0.3, jnp.float32)
                    for s in ((2, c, f),) * (2 if body == "gated_silu" else 1) + ((2, f, c),))
    group_sizes = jnp.asarray([live // 3, live - live // 3], jnp.int32)
    at = jnp.arange(rows)
    dead = at >= -(-live // chunk) * chunk                    # the chunks nobody may visit
    if scatter == "whole":
        dead = dead & False
    poisoned_tokens = jnp.where(dead & (at % 2 == 0), n + 5, tokens)

    def plain(xd, weights, *experts):
        ys = moe_ops._expert_body(xd[tokens], experts, group_sizes, jnp.float32)
        w = jnp.where(at < live, weights, 0.0)
        return y0.at[tokens].add(ys * w[:, None])

    def chunked(xd, weights, *experts):
        ys = moe_ops._expert_body(xd[tokens], experts, group_sizes, jnp.float32)
        w = jnp.where(dead, jnp.nan, jnp.where(at < live, weights, 0.0))
        return moe_ops._add_pass_rows(y0, jnp.where(dead[:, None], jnp.nan, ys), w,
                                      poisoned_tokens, jnp.int32(live), chunk)

    np.testing.assert_allclose(jax.jit(chunked)(xd, weights, *experts),
                               plain(xd, weights, *experts), rtol=1e-5, atol=1e-6)
    every = tuple(range(2 + len(experts)))
    got = jax.jit(jax.grad(lambda *a: jnp.sum(probe * chunked(*a)), argnums=every))(
        xd, weights, *experts)
    want = jax.grad(lambda *a: jnp.sum(probe * plain(*a)), argnums=every)(
        xd, weights, *experts)
    for g, h in zip(got, want):
        np.testing.assert_allclose(g, h, rtol=1e-5, atol=1e-6)
    assert bool(np.any(np.asarray(got[0]))) == (live > 0)


def held_layer(body):
    """A held layer's inputs at shapes no two of which agree: 16 experts,
    experts 4-7 held, tokens and matrices bfloat16 as a model hands them after
    its cast. (run, (x, weights, *matrices)); 45 of the 192 pairs are held."""
    n, c, f, e, k, held = 64, 32, 24, 16, 3, (4, 4)
    r = np.random.default_rng(3)
    idx = jnp.asarray(r.integers(0, e, size=(n, k)), jnp.int32)
    assert int(np.sum((np.asarray(idx) >= 4) & (np.asarray(idx) < 8))) == 45
    x = jnp.asarray(r.normal(size=(n, c)), jnp.bfloat16)
    up_like = ((held[1], c, f),) * (2 if body == "gated_silu" else 1)
    w = tuple(jnp.asarray(r.normal(size=s) * 0.3, jnp.bfloat16)
              for s in up_like + ((held[1], f, c),))
    weights = jnp.asarray(r.uniform(0.1, 1.0, size=(n, k)), jnp.float32)
    run = lambda x, weights, *w: moe_ops.dropless_moe(
        x, idx, weights, w, held=held, num_experts=e)
    return run, (x, weights, *w)


@pytest.mark.parametrize("body", ["relu2", "gated_silu"])
def test_a_one_pass_backward_hands_on_what_the_accumulators_would(body, monkeypatch):
    """Held pairs that fit one pass: dx, dw and every matrix's gradient are,
    in the operands' dtypes (bfloat16, float32, bfloat16) and to the bit, what
    the pass's cotangents give when added to float32 zeros and narrowed again
    — the arithmetic of the accumulators this path no longer makes. (The sum
    turns a cotangent's -0.0 into 0.0: equal as numbers, which is what is
    compared.)"""
    run, operands = held_layer(body)
    seen = []
    with monkeypatch.context() as m:
        m.setattr(moe_ops, "_held_passes",
                  lambda *a: seen.append(a) or jnp.zeros(a[0].shape, jnp.float32))
        run(*operands)
    xd, flat_weights, experts, order, starts, ends, k, rows = seen[0]
    assert int(ends[-1]) == 45 <= rows
    g = jnp.asarray(np.random.default_rng(4).normal(size=xd.shape), jnp.float32)
    through = lambda passes: jax.vjp(passes, xd, flat_weights, experts)[1](g)
    got = through(lambda a, b, c: moe_ops._held_passes(a, b, c, order, starts, ends, k, rows))
    once = through(lambda a, b, c: moe_ops._held_pass(
        jnp.zeros_like(g), a, b, c, order, starts, ends, 0, k, rows))
    want = jax.tree_util.tree_map(
        lambda d: (jnp.zeros(d.shape, jnp.float32) + d.astype(jnp.float32)).astype(d.dtype),
        once)
    assert [d.dtype for d in jax.tree_util.tree_leaves(got)] == (
        [jnp.bfloat16, jnp.float32] + [jnp.bfloat16] * len(experts))
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.any(np.asarray(a, np.float32))
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def hlo_computations(hlo_text):
    """name -> body, of an HLO module's text."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{$\n(.*?)^\}$", hlo_text, re.M | re.S)}


def hlo_reach(computations, name, through):
    """The text of `name` and, once a mention, of every computation it
    reaches by the attributes `through` (`to_apply`, `body`, `condition`)."""
    return computations[name] + "".join(
        hlo_reach(computations, callee, through) for attribute in through
        for callee in re.findall(rf"\b{attribute}=%?([\w.\-]+)", computations[name]))


@pytest.mark.parametrize("scatter_chunk_rows, walks_a_pass", [(256, 2), (8, 1)])
@pytest.mark.parametrize("body", ["relu2", "gated_silu"])
def test_the_first_pass_is_straight_line_code_and_the_accumulators_are_the_overflow_s(
        body, scatter_chunk_rows, walks_a_pass, monkeypatch):
    """The lowered text of a held layer's value and gradient, with the
    kernels as a TPU takes them: every grouped matmul of a pass that runs
    whatever the routing — forward one a matrix, backward the recomputed one,
    a dx and a dW a matrix — is reached from the entry by calls alone; of the
    loops outside a `conditional` one holds kernels, the forward's overflow,
    whose carry is the (N, C) sum, and the others are the walks over a pass's
    live chunks (the combine and its pull-back — the pull-back alone where a
    chunk is larger than `SCATTER_CHUNK_ROWS` and the scatter-add is one
    call), which hold no kernel and no float32 array of a matrix's or of a
    pass's (rows, C) shape; the backward's untaken branch hands its operands
    through; and no float32 array of a matrix's shape exists outside the
    taken branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # the kernel's route asks
    monkeypatch.setattr(pallas_gmm, "ROW_TILE", 16)              # 192 rows: 12 chunks of 16
    monkeypatch.setattr(moe_ops, "SCATTER_CHUNK_ROWS", scatter_chunk_rows)
    run, operands = held_layer(body)
    probe = jnp.ones(operands[0].shape, jnp.float32)
    text = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(probe * run(*a)), argnums=tuple(range(len(operands)))
    )).trace(*operands).lower(lowering_platforms=("tpu",)).as_text(dialect="hlo")
    computations = hlo_computations(text)
    entry = re.search(r"^ENTRY %?([\w.\-]+)", text, re.M).group(1)
    kernels = lambda t: t.count('custom_call_target="tpu_custom_call"')
    matrices = len(operands) - 2
    straight = hlo_reach(computations, entry, ("to_apply",))
    assert kernels(straight) == 4 * matrices

    (identity, overflow), = [
        names.replace("%", "").split(", ")
        for names in re.findall(r"conditional\(.*branch_computations=\{([^}]*)\}", straight)]
    assert not re.search(r"custom-call|convert|while", computations[identity])
    overflow = hlo_reach(computations, overflow, ("to_apply", "body", "condition"))
    assert kernels(overflow) == 3 * matrices and " while(" in overflow
    wide = [rf"f32\[{','.join(map(str, w.shape))}\]" for w in operands[2:]]
    assert all(re.search(shape, overflow) for shape in wide)

    loops = [hlo_reach(computations, loop, ("to_apply", "body", "condition"))
             for loop in re.findall(r"^.* while\(.*body=%?([\w.\-]+)", straight, re.M)]
    with_kernels = [loop for loop in loops if kernels(loop)]
    assert [kernels(loop) for loop in with_kernels] == [matrices]   # the forward's overflow
    walks = [loop for loop in loops if not kernels(loop)]
    assert len(walks) == walks_a_pass
    pass_rows = rf"f32\[{3 * operands[0].shape[0]},{operands[0].shape[1]}\]"
    assert not any(re.search(shape, walk) for walk in walks for shape in wide + [pass_rows])
    outside = straight + "".join(loops)
    assert kernels(outside) == 5 * matrices
    # scatter-adds in one call make their float32 addends for all rows, forward
    whole_addends = [pass_rows] if walks_a_pass == 2 else []
    assert not any(re.search(shape, outside) for shape in wide + whole_addends)


def test_held_all_is_the_plain_dispatch_and_a_wrong_share_is_refused():
    n, c, f, e, k = 16, 8, 4, 4, 2
    r = np.random.default_rng(2)
    x = jnp.asarray(r.normal(size=(n, c)), jnp.float32)
    w = (jnp.asarray(r.normal(size=(e, c, f)), jnp.float32),
         jnp.asarray(r.normal(size=(e, f, c)), jnp.float32))
    weights = jnp.asarray(r.uniform(0.1, 1.0, size=(n, k)), jnp.float32)
    idx = jnp.asarray(r.integers(0, e, size=(n, k)), jnp.int32)
    plain = moe_ops.dropless_moe(x, idx, weights, w, compute_dtype=jnp.float32)
    all_held = moe_ops.dropless_moe(x, idx, weights, w, held=(0, e), num_experts=e,
                                    compute_dtype=jnp.float32)
    np.testing.assert_array_equal(plain, all_held)
    np.testing.assert_allclose(plain, relu2_loop(x, idx, weights, *w, (0, e)),
                               rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="held="):
        moe_ops.dropless_moe(x, idx, weights, w, held=(0, 2), num_experts=8)


# ------------------------------------------------------------------ #
# 512 router outputs, top-10, 32 held (qwen3-next-80b-a3b.resident-16k)


def test_the_passes_of_a_share_of_512_experts_are_sized_from_the_shapes():
    """163 840 pairs a layer sorted over 512 experts of which 32 are held: a
    pass of 20 480 rows (twice the even share), 80 row tiles of 256, chunks of
    1280 rows — a sixteenth of the pass."""
    pairs = 16384 * 10
    rows = moe_ops.held_pass_rows(pairs, 512, 32)
    assert rows == 20480 == 2 * pairs * 32 // 512
    assert moe_ops.held_row_chunk(rows) == 1280
    # at even routing 10 240 pairs are held: half the pass's tiles, chunks
    assert int(moe_ops.held_row_tiles(jnp.int32(10240), pairs, 512, 32)) == 40
    assert int(moe_ops.held_row_chunks(jnp.int32(10240), pairs, 512, 32)) == 8
    # more than twice the even share overflows into a second pass
    assert int(moe_ops.held_row_chunks(jnp.int32(20481), pairs, 512, 32)) == 17


@pytest.mark.parametrize("first", [0, 480])
def test_a_softmax_top10_of_512_held_32_matches_the_loop_over_the_held(first):
    """The softmax router at 512 outputs and k = 10, renormalised, through the
    held dispatch at 32 experts of width 8: values and the gradients of the
    tokens, the weights and every matrix, against every held expert on every
    token and a mask; a token's ten experts are distinct and its weights sum
    to one."""
    n, c, f, e, k, held = 96, 16, 8, 512, 10, (first, 32)
    r = np.random.default_rng(7)
    x = jnp.asarray(r.normal(size=(n, c)), jnp.float32)
    w = tuple(jnp.asarray(r.normal(size=s) * 0.3, jnp.float32)
              for s in ((32, c, f), (32, c, f), (32, f, c)))
    logits = jnp.asarray(r.normal(size=(n, e)) * 2.0, jnp.float32)
    probs, weights, idx = moe_ops.topk_route(logits, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    assert all(len(set(row)) == k for row in np.asarray(idx).tolist())
    np.testing.assert_allclose(np.sum(weights, axis=-1), 1.0, atol=1e-6)
    balance, _ = moe_ops.router_aux_losses(logits, probs, idx)
    assert 0.9 < float(balance) < 3.0                 # 1.0 at perfect balance
    on_held = int(np.sum((np.asarray(idx) >= first) & (np.asarray(idx) < first + 32)))
    assert 20 < on_held < 120                         # about 960 / 16
    held_moe = lambda x, weights, *w: moe_ops.dropless_moe(
        x, idx, weights, w, held=held, num_experts=e, compute_dtype=jnp.float32)
    loop = lambda x, weights, *w: gated_silu_loop(x, idx, weights, *w, held)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(held_moe(x, weights, *w), loop(x, weights, *w),
                                   rtol=1e-4, atol=1e-5)
        scalar = lambda f: (lambda *a: jnp.sum(jnp.sin(f(*a))))
        got = jax.grad(scalar(held_moe), argnums=range(5))(x, weights, *w)
        want = jax.grad(scalar(loop), argnums=range(5))(x, weights, *w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
