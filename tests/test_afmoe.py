"""Trinity (model_zoo/transformer/afmoe.py: attention gated on its output, q/k
head norms, rotary positions in the sliding layers only, four norms a layer, a
dense layer, held gated-SiLU experts behind a sigmoid router with a centred
selection bias kept as the running sum of its updates, a shared expert)
against its plain reference (benchmark/reference/afmoe.py) on seeded weights,
at a tiny size on the CPU: hidden 64, published layers 0, 2, 3 at a period of
2 (dense sliding, sparse sliding with a window of 8, sparse full), 4/2 heads
of 16, 16 experts top-3 of which experts 4-7 are held, vocabulary 256, 36
tokens, float32.

The benchmark's own comparison, and the departures it must catch, are in
`tests/test_afmoe_check.py`.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_lm, common
from elasticdl_tpu.ops import pallas_attention
from tests import zoo_lm
from tests.conftest import pallas_calls

TINY = zoo_lm.preset("tiny-lm-afmoe.json")
NORMS = ("final_norm", "attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm",
         "q_norm", "k_norm")
MATRICES = ("wq", "wk", "wv", "wg", "wo", "mlp_gate", "mlp_up", "mlp_down",
            "shared_gate", "shared_up", "shared_down", "w_gate", "w_up", "w_down")
LEAVES = ("embed", "head", "moe_router") + NORMS + MATRICES

reference = common.load_module("reference", "afmoe")
flops = common.load_module("flops", "afmoe")
driver = common.load_module("drivers", "resident_lm_model")
departures = common.load_module("rehearse", "departures_afmoe")

lm = zoo_lm.ZooLM(
    "afmoe", tiny=TINY, reference=reference, driver=driver, departures=departures,
    seq=36, mutable=("router_state", "attn"), training=True,
    # router logits of order one, every norm's weight away from one (the head
    # norms' too: a norm after the rotation is then another function),
    # projections large enough that the gate is far from a half
    lively=[(("moe_router",), zoo_lm.scaled(8.0)),
            (NORMS, zoo_lm.jittered),
            (MATRICES, zoo_lm.scaled(6.0))],
    # the check's cases run published layers 0 and 3: a dense sliding layer
    # and a sparse full one — both kinds of attention, both feed-forwards
    short={"num_hidden_layers": 2, "kept_layers": "0,3"})
# a selection bias that is not zero
BIAS = jnp.asarray(np.random.default_rng(2).normal(size=(2, 16)) * 0.02, jnp.float32)


def zoo():
    return lm.zoo


def cfg_of(**more):
    return zoo().custom_model(**lm.tiny_params(**more)).cfg


def router_state(bias):
    layers = bias.shape[0]
    zeros = jnp.zeros((layers,), jnp.int32)
    return {"router_state": {"expert_bias": bias, "held_passes": zeros,
                             "held_row_tiles": zeros, "held_row_chunks": zeros,
                             "pairs_held_share": jnp.zeros((layers,), jnp.float32)},
            "attn": {"kv_block_visits": jnp.zeros((2,), jnp.int32),
                     "kv_block_visits_causal": jnp.zeros((2,), jnp.int32)}}


@pytest.fixture(scope="module")
def gradients():
    """(program's, reference's) loss terms and gradients of one batch from
    the same lively parameters and a selection bias that is not zero."""
    return lm.gradients(
        lambda p, batch, hp: reference.loss_terms(p, batch, hp, None, BIAS)[:2],
        router_state(BIAS))


# ------------------------------------------------------------------ #
# the model against the reference


@pytest.mark.parametrize("term", ["loss", "loss_ce"])
def test_loss_terms_match_reference(gradients, term):
    ((total, got), _), ((ref_total, want), _) = gradients
    got, want = ({**got, "loss": total}[term], {**want, "loss": ref_total}[term])
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    assert float(want) > np.log(TINY["vocab_size"]) - 0.5          # untrained


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_reference(gradients, leaf):
    (_, got), (_, want) = gradients
    assert got[leaf].shape == want[leaf].shape
    assert np.linalg.norm(want[leaf]) > 0
    assert check_lm._rel_l2(np.asarray(got[leaf]), np.asarray(want[leaf])) < 1e-4


def test_kinds_follow_the_published_index_not_the_position():
    cfg = zoo().Config()
    assert [cfg.kind(l) for l in range(32)] == ["sliding", "sliding", "sliding", "full"] * 8
    assert [cfg.is_dense(l) for l in range(4)] == [True, True, False, False]
    cut = zoo().custom_model(num_hidden_layers=5, kept_layers="0,2,3,4,5").cfg
    assert [cut.kind(l) for l in cut.layers] == ["sliding", "sliding", "full", "sliding", "sliding"]
    assert (cut.dense_layers, cut.sparse_layers) == (1, 4)
    hp = reference.hyper({**lm.tiny_params(), "num_hidden_layers": "5",
                          "kept_layers": "0,2,3,4,5", "global_attn_every_n_layers": "4"})
    assert [reference.is_full(l, hp) for l in hp["layers"]] == [False, False, True, False, False]
    assert hp["moe_layers"] == 4
    with pytest.raises(ValueError, match="kept_layers"):
        zoo().custom_model(num_hidden_layers=4, kept_layers="0,2,3,4,5")


# ------------------------------------------------------------------ #
# the attention sub-block, by hand


def one_attention_layer(seed=7, tokens=21):
    cfg = cfg_of()
    r = np.random.default_rng(seed)
    c, heads, kv, d = 64, 4, 2, 16
    shapes = {"attn_norm": (c,), "wq": (c, heads * d), "wk": (c, kv * d), "wv": (c, kv * d),
              "wg": (c, heads * d), "q_norm": (d,), "k_norm": (d,), "wo": (heads * d, c)}
    p = {k: jnp.asarray(r.uniform(0.5, 1.5, s) if k.endswith("norm")
                        else r.normal(size=s) * 0.4, jnp.float32)
         for k, s in shapes.items()}
    x = jnp.asarray(r.normal(size=(2, tokens, c)), jnp.float32)
    return cfg, p, x


def by_hand_attention(p, x, kind, cfg, gate_input=None, gate_before_wo=True,
                      norm_before_rotation=True):
    """Plain attention, every query head with its key-value head repeated:
    q/k norms, then the rotation (sliding only), the mask from positions, the
    gate from the NORMED input on the output BEFORE W_o."""
    m = zoo()
    b, t, _ = x.shape
    heads, kv, d, w = (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
                       cfg.sliding_window)
    h = m.rmsnorm(x, p["attn_norm"], cfg.rms_norm_eps)
    q = (h @ p["wq"]).reshape(b, t, heads, d)
    k = jnp.repeat((h @ p["wk"]).reshape(b, t, kv, d), heads // kv, axis=2)
    v = jnp.repeat((h @ p["wv"]).reshape(b, t, kv, d), heads // kv, axis=2)
    norm = lambda q, k: (m.rmsnorm(q, p["q_norm"], cfg.rms_norm_eps),
                         m.rmsnorm(k, p["k_norm"], cfg.rms_norm_eps))
    turn = lambda q, k: ((m.rope(q, cfg.rope_theta), m.rope(k, cfg.rope_theta))
                         if kind == "sliding" else (q, k))
    q, k = turn(*norm(q, k)) if norm_before_rotation else norm(*turn(q, k))
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    visible = (j <= i) & ((j > i - w) if kind == "sliding" else True)
    scores = jnp.where(visible, jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d), -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v).reshape(b, t, -1)
    g = jax.nn.sigmoid((h if gate_input is None else gate_input) @ p["wg"])
    return (out * g) @ p["wo"] if gate_before_wo else (out @ p["wo"]) * g


@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_attention_is_gated_normed_and_rotated_as_by_hand(kind):
    cfg, p, x = one_attention_layer()
    hp = reference.hyper(lm.tiny_params())
    with jax.default_matmul_precision("highest"):
        want = by_hand_attention(p, x, kind, cfg)
        got, gate_mean = zoo().attention(p, x, kind, cfg)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(reference.attention(p, x, kind == "full", hp), want,
                                   rtol=2e-4, atol=2e-5)
        assert gate_mean.shape == (2,) and np.all(np.abs(np.asarray(gate_mean) - 0.5) < 0.2)
        # the gate's place (before W_o), its input (the normed h) and the
        # norms' place (before the rotation) each matter at this size
        others = [by_hand_attention(p, x, kind, cfg, gate_before_wo=False),
                  by_hand_attention(p, x, kind, cfg, gate_input=x)]
        if kind == "sliding":
            others.append(by_hand_attention(p, x, kind, cfg, norm_before_rotation=False))
        for other in others:
            assert float(jnp.max(jnp.abs(other - want))) > 1e-2 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_a_full_layer_takes_no_positions(kind, monkeypatch):
    """Another rotary table (theta x 3) leaves a full layer's output as it is,
    to the bit, and changes a sliding layer's. (A constant added to every
    position cannot tell the two apart: rotary scores depend on i − j alone,
    so a sliding layer does not see it either.)"""
    cfg, p, x = one_attention_layer()
    plain = zoo().rope
    before, _ = zoo().attention(p, x, kind, cfg)
    monkeypatch.setattr(zoo(), "rope", lambda x, theta: plain(x, 3 * theta))
    after, _ = zoo().attention(p, x, kind, cfg)
    moved = float(jnp.max(jnp.abs(after - before)))
    if kind == "full":
        assert moved == 0.0
    else:
        assert moved > 1e-3 * float(jnp.max(jnp.abs(before)))
    # positions reach a full layer through the mask: a later token moves nothing earlier
    changed, _ = zoo().attention(p, x.at[:, 12].add(1.0), kind, cfg)
    assert float(jnp.max(jnp.abs((changed - after)[:, :12]))) == 0.0


@pytest.mark.parametrize("kind,distance,sees", [
    ("sliding", 7, True),       # key i − W + 1: the window's last key
    ("sliding", 8, False),      # key i − W: one too far
    ("full", 8, True), ("full", 20, True)])
def test_the_window_s_two_edges(kind, distance, sees):
    """Window 8: query i sees keys i − 7 … i. Moving token j moves the output
    at i = j + 7 and not at i = j + 8; a full layer sees every earlier key."""
    cfg, p, x = one_attention_layer(tokens=30)
    assert cfg.sliding_window == 8
    j = 5
    hp = reference.hyper(lm.tiny_params())
    for attend in (lambda x: zoo().attention(p, x, kind, cfg)[0],
                   lambda x: reference.attention(p, x, kind == "full", hp)):
        moved = jnp.abs(attend(x.at[:, j].add(1.0)) - attend(x))[:, j + distance]
        assert (float(jnp.max(moved)) > 1e-6) == sees


def test_a_layer_has_four_norms_and_writes_through_two_of_them():
    """x + w_post · unit(f(norm_pre(x))): what a sub-block adds has the RMS of
    its post-norm's weight whatever the sub-block's own scale."""
    m = zoo()
    cfg = cfg_of()
    params = jax.tree_util.tree_map(lambda a: a, lm.params())
    p = {k: params[k][0] for k in m.ATTN_KEYS + m.DENSE_KEYS}
    p = {**p, "post_attn_norm": jnp.full((64,), 0.25), "post_mlp_norm": jnp.full((64,), 2.0)}
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 12, 64)), jnp.float32)
    y, _ = m.attention(p, x, "sliding", cfg)
    added = m.post_attn_norm(p, y, cfg)
    np.testing.assert_allclose(jnp.sqrt(jnp.mean(added ** 2, axis=-1)), 0.25, rtol=1e-3)
    out, _, stats = m.block(p, x, None, "sliding", cfg)
    assert stats is None
    mid = x + added
    np.testing.assert_allclose(jnp.sqrt(jnp.mean((out - mid) ** 2, axis=-1)), 2.0, rtol=1e-3)
    # ten times the output projection: the same update of the stream
    louder, _, _ = m.block({**p, "wo": 10 * p["wo"]}, x, None, "sliding", cfg)
    np.testing.assert_allclose(louder, out, rtol=1e-3, atol=2e-3)


def test_the_embedding_is_multiplied_by_the_root_of_the_width():
    m, params = zoo(), lm.params()
    tokens = jnp.asarray([[3, 200, 7]])
    got = m.embed(params, tokens, cfg_of())
    np.testing.assert_allclose(got, 8.0 * params["embed"][tokens[0]][None], rtol=1e-6)
    assert math.sqrt(2048) == pytest.approx(45.2548, abs=1e-4)


# ------------------------------------------------------------------ #
# the router and its bias


def one_router(seed=5):
    r = np.random.default_rng(seed)
    p = {"mlp_norm": jnp.asarray(r.uniform(0.5, 1.5, (64,)), jnp.float32),
         "moe_router": jnp.asarray(r.normal(size=(64, 16)), jnp.float32)}
    x = jnp.asarray(r.normal(size=(2, 20, 64)), jnp.float32)
    return p, x


def test_route_scale_multiplies_renormalised_weights():
    p, x = one_router()
    bias = jnp.zeros((16,), jnp.float32)
    _, weights, idx = zoo().route(p, x, bias, cfg_of())
    assert weights.shape == idx.shape == (40, 3)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.826, rtol=1e-5)
    _, unscaled, _ = zoo().route(p, x, bias, cfg_of(route_scale=1.0))
    np.testing.assert_allclose(weights, 2.826 * unscaled, rtol=1e-6)


def test_the_bias_selects_and_does_not_weigh():
    p, x = one_router()
    cfg = cfg_of()
    _, plain_w, plain_idx = zoo().route(p, x, jnp.zeros((16,)), cfg)
    bias = jnp.zeros((16,)).at[11].set(5.0)          # expert 11 into every selection
    _, weights, idx = zoo().route(p, x, bias, cfg)
    assert np.all(np.any(np.asarray(idx) == 11, axis=-1))
    assert not np.all(np.any(np.asarray(plain_idx) == 11, axis=-1))
    # its weight is its SCORE's share, far under the 5.0 it was selected by
    h = zoo().rmsnorm(x, p["mlp_norm"], cfg.rms_norm_eps).reshape(-1, 64)
    scores = jax.nn.sigmoid(h @ p["moe_router"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(weights, 2.826 * chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-5)


def test_the_centred_update_leaves_every_selection_where_the_uncentred_one_puts_it():
    """The state is the running sum a of the updates d; the router adds
    a − mean(a), which is ISSUE 44's `b ← b + d − mean(d)` from zero, and picks
    what the sum itself, uncentred, would pick."""
    from elasticdl_tpu.ops import moe as moe_ops

    p, x = one_router()
    cfg = cfg_of()
    h = zoo().rmsnorm(x, p["mlp_norm"], cfg.rms_norm_eps).reshape(-1, 64)
    total = recurrence = jnp.zeros((1, 16), jnp.float32)
    for step in range(6):
        _, _, idx = zoo().route(p, x, total[0], cfg)
        _, _, idx_u = moe_ops.sigmoid_topk_route(
            h @ p["moe_router"], total[0], cfg.num_experts_per_tok, cfg.route_scale)
        np.testing.assert_array_equal(np.sort(idx, axis=-1), np.sort(idx_u, axis=-1))
        load = np.bincount(np.asarray(idx).ravel(), minlength=16)
        delta = 1e-3 * np.sign(load.mean() - load)
        recurrence = recurrence + (delta - delta.mean())[None]
        np.testing.assert_allclose(
            reference.bias_update(total, check_lm.chosen_mask(np.asarray(idx)[None], 16)),
            total + delta[None], atol=1e-9)
        total = zoo().updated_bias(total, idx[None], cfg)
        np.testing.assert_allclose(zoo().centred(total), recurrence, atol=1e-8)
    centred = zoo().centred(total)
    assert abs(float(centred.sum())) < 1e-7 and float(jnp.abs(centred).max()) > 2e-3


def test_one_sign_of_the_update_moves_one_entry_of_the_state():
    """Why the state is the sum and not its centred form: the benchmark's
    check counts the ENTRIES of the state that differ from the reference's,
    and one expert whose load sits at the mean may take either sign."""
    cfg = cfg_of()
    idx = np.random.default_rng(0).integers(0, 16, (1, 40, 3))
    load = np.bincount(idx.ravel(), minlength=16)             # the mean is 7.5
    source = int(np.flatnonzero(load == 8)[0])                # one pair fewer: the other sign
    target = int(np.flatnonzero((load != 7) & (np.arange(16) != source))[0])
    nudged = idx.copy()
    nudged[tuple(np.argwhere(idx == source)[0])] = target
    zero = jnp.zeros((1, 16), jnp.float32)
    a = zoo().updated_bias(zero, jnp.asarray(idx), cfg)
    b = zoo().updated_bias(zero, jnp.asarray(nudged), cfg)
    assert np.flatnonzero(np.asarray(a != b)[0]).tolist() == [source]
    assert int(jnp.sum(jnp.abs(zoo().centred(a) - zoo().centred(b)) > 1e-7)) == 16


def test_the_step_reports_its_term_and_evaluation_reads_the_gates():
    spec, trainer = lm.trainer(warmup_steps=1)
    data = lm.batches(steps=1)[0]
    state = trainer.init_state(data)
    bias = lambda s: np.asarray(s.extra_vars["router_state"]["expert_bias"])
    assert bias(state).shape == (2, 16) and not bias(state).any()
    results = trainer.metric_results(
        trainer.eval_step(state, data, trainer.new_metric_states()))
    assert set(results) == {"token_accuracy", "gate_mean_sliding", "gate_mean_full", "loss"}
    assert abs(results["gate_mean_sliding"] - 0.5) < 0.05     # a half at the seed
    assert abs(results["gate_mean_full"] - 0.5) < 0.05
    assert not bias(state).any()                       # evaluation leaves the bias alone
    state, logs = trainer.train_step(state, data)
    assert set(logs) == {"loss", "loss_ce"}
    assert set(np.unique(bias(state))) <= {np.float32(-1e-3), np.float32(0), np.float32(1e-3)}
    assert bias(state).any()                           # one step's signs, uncentred
    counted = jax.device_get(state.extra_vars)
    np.testing.assert_array_equal(counted["router_state"]["held_passes"], [1, 1])
    share = counted["router_state"]["pairs_held_share"]
    assert share.shape == (2,) and np.all((share > 0) & (share < 1))


def test_custom_model_ignores_the_harness_keys_and_trains():
    spec, trainer = lm.trainer(warmup_steps=1)
    model = zoo().custom_model(field_vocab="512", **lm.tiny_params())
    assert model.cfg == spec.model.cfg
    assert (model.cfg.held_experts, model.cfg.num_experts, model.cfg.held) == (4, 16, (4, 4))
    data = lm.batches(steps=1)[0]
    state = trainer.init_state(data)
    losses = []
    for _ in range(8):
        state, m = trainer.train_step(state, data)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3


# ------------------------------------------------------------------ #
# kernels, counters, recomputation


@pytest.mark.parametrize("kept,forward_calls", [((), [4, 2]), (("full",), [4, 1]),
                                                (("sliding", "full"), [2, 1])])
def test_the_kinds_that_keep_their_flash_residuals_run_one_forward_kernel(
        kept, forward_calls, monkeypatch):
    """`forward` checkpoints each layer, those of `KEEP_RESIDUALS_KINDS` under
    `pallas_attention.KEEP_RESIDUALS`: on the kernel route (sequence 64, window
    16: two banded layers and a full one) a step's jaxpr holds ONE forward
    kernel for a layer that keeps them and two for one that does not (counted
    in the jaxpr, not run)."""
    monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
    monkeypatch.setenv("EDL_FLASH", "1")
    spec, _ = lm.fresh_trainer(sliding_window=16)
    monkeypatch.setattr(zoo(), "KEEP_RESIDUALS_KINDS", kept)
    batch, params = lm.batches(steps=1, seq=64)[0], lm.params()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: lm.terms(spec, p, batch, router_state(BIAS))["loss"]))(params).jaxpr
    assert [pallas_calls(jaxpr, name) for name in (
        "flash_attention_swa_fwd", "flash_attention_fwd")] == forward_calls
    assert [pallas_calls(jaxpr, name) for name in (
        "flash_attention_swa_bwd", "flash_attention_bwd")] == [2, 1]


def test_the_program_counts_its_kernels_grid_steps(monkeypatch):
    """`attn/kv_block_visits` beside `attn/kv_block_visits_causal`, per kind
    [sliding, full] (blocks of 16 here, so that 64 tokens are four of them)."""
    for name in ("DEFAULT_BLOCK_Q", "DEFAULT_BLOCK_K"):
        monkeypatch.setattr(pallas_attention, name, 16)
    spec, trainer = lm.fresh_trainer(sliding_window=16)
    batch = lm.batches(steps=1, seq=64)[0]
    state = trainer.init_state(batch)
    for _ in range(2):
        state, _ = trainer.train_step(state, batch)
    banded, causal = pallas_attention.kv_block_visits(64, 64, 16, 16, jnp.float32)
    assert (banded, causal) == (7, 10)
    counted = jax.device_get(state.extra_vars)
    assert counted["attn"]["kv_block_visits"].tolist() == [2 * 2 * banded, 2 * causal]
    assert counted["attn"]["kv_block_visits_causal"].tolist() == [2 * 2 * causal, 2 * causal]


# ------------------------------------------------------------------ #
# parameter counts: the card's, and the cut's


def _published_params(**more):
    cfg = zoo().Config()
    names = ("vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size",
             "num_attention_heads", "num_key_value_heads", "head_dim", "sliding_window",
             "num_experts_per_tok", "moe_intermediate_size")
    return ({k: str(getattr(cfg, k)) for k in names} | {"num_experts": "128"}
            | {k: str(v) for k, v in more.items()})


@pytest.mark.parametrize("more,count", [
    ({}, 26_123_970_560),
    ({"num_hidden_layers": 5, "kept_layers": "0,2,3,4,5", "num_experts": 16,
      "router_experts": 128, "vocab_size": 25024}, 705_473_792)])
def test_parameter_count_uncut_and_at_the_cut(more, count):
    params = _published_params(**more)
    assert flops.parameter_count(params) == count
    model = zoo().custom_model(**params)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(shapes["params"])) == count


def test_the_card_s_active_parameters():
    assert flops.active_parameter_count(_published_params()) == 3_064_463_360


# ------------------------------------------------------------------ #
# the share of a deployment, tied to the whole (model-configs guide §4)


def test_eight_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """One sparse feed-forward at 16 experts top-3: the routed parts that 8
    shares of 2 experts compute (the program's held dispatch, the shared
    expert taken away) plus the shared expert ONCE equal what the reference
    gives for the layer with every expert held — as the cell's eight shares of
    16 make its 128 — and the reference, given a share, gives that share's
    part."""
    m = zoo()
    r = np.random.default_rng(3)
    c, f, e = 64, 24, 16
    normal = lambda *shape: r.normal(size=shape) * 0.2
    whole = {"mlp_norm": r.uniform(0.5, 1.5, (c,)), "moe_router": r.normal(size=(c, e)),
             "shared_gate": normal(c, f), "shared_up": normal(c, f),
             "shared_down": normal(f, c), "w_gate": normal(e, c, f),
             "w_up": normal(e, c, f), "w_down": normal(e, f, c)}
    whole = {k: jnp.asarray(v, jnp.float32) for k, v in whole.items()}
    x = jnp.asarray(r.normal(size=(2, 9, c)), jnp.float32)
    bias = jnp.asarray(r.normal(size=(e,)) * 0.05, jnp.float32)
    hp_whole = reference.hyper(lm.tiny_params(num_experts=16, first_expert=0))
    with jax.default_matmul_precision("highest"):
        want, _, _ = jax.jit(lambda p, x: reference.moe(p, x, bias, None, hp_whole))(whole, x)
        shared = m.gated_mlp(
            m.rmsnorm(x, whole["mlp_norm"], 1e-5).reshape(-1, c), whole["shared_gate"],
            whole["shared_up"], whole["shared_down"], jnp.float32).reshape(x.shape)
        total = shared
        for share in range(8):
            cfg = cfg_of(num_experts=2, first_expert=2 * share)
            held = slice(2 * share, 2 * share + 2)
            part = {**whole, "w_gate": whole["w_gate"][held], "w_up": whole["w_up"][held],
                    "w_down": whole["w_down"][held]}
            y, _ = jax.jit(lambda p, x: m.moe(p, x, bias, cfg))(part, x)
            total = total + (y - shared)
            hp = reference.hyper(lm.tiny_params(num_experts=2, first_expert=2 * share))
            ref_part, _, _ = jax.jit(lambda p, x: reference.moe(p, x, bias, None, hp))(part, x)
            np.testing.assert_allclose(y, ref_part, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(want - shared).max()) > 0.1
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
