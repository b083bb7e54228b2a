"""Kimi Linear (model_zoo/transformer/kimi_linear.py: a delta-rule linear
attention with a decay for every channel in three layers of four, latent
attention without positions in the fourth, a dense layer, held gated-SiLU
experts behind a sigmoid router with a selection bias, a shared expert)
against its plain reference (benchmark/reference/kimi_linear.py, whose
recurrence is token by token) on seeded weights, at a tiny size on the CPU:
hidden 48, five layers as published (layer 1 dense, layer 4 latent), 4 linear
heads of 16 under chunks of 16, 4 latent heads of 16 + 8 beside 16, 16 experts
top-3 of which experts 4-7 are held, vocabulary 256, float32.

The benchmark's own comparison, and the departures it must catch, are in
`tests/test_kimi_linear_check.py`, the zoo contract in
`tests/test_kimi_linear_contract.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_lm, common
from tests import zoo_lm

TINY = zoo_lm.preset("tiny-lm-kda.json")
SEQ = 40
NORMS = ("final_norm", "kda_norm", "kda_onorm", "attn_norm", "kv_a_norm", "mlp_norm",
         "moe_norm")
KDA_MATRICES = ("kda_wq", "kda_wk", "kda_wv", "kda_conv_q", "kda_conv_k", "kda_conv_v",
                "kda_f_a", "kda_f_b", "kda_beta", "kda_g_a", "kda_g_b")
MATRICES = KDA_MATRICES + ("q_proj", "kv_a", "kv_b", "mlp_gate", "mlp_up", "shared_gate",
                           "shared_up", "w_gate", "w_up")
RESIDUAL_WRITES = ("kda_wo", "wo", "mlp_down", "shared_down", "w_down")
VECTORS = ("kda_A_log", "kda_dt_bias")
LEAVES = ("embed", "head", "moe_router") + NORMS + MATRICES + RESIDUAL_WRITES + VECTORS

reference = common.load_module("reference", "kimi_linear")
flops = common.load_module("flops", "kimi_linear")
driver = common.load_module("drivers", "resident_lm_model")
departures = common.load_module("rehearse", "departures_kimi_linear")

# router logits of order one, every norm's weight away from one, projections
# large enough that decay, write strength and both gates differ from token to
# token and attention is far from a running mean; the matrices that write to
# the residual stream (seeded 7 times smaller) brought to the others' size
LIVELY = [(("moe_router",), zoo_lm.scaled(0.3)),
          (NORMS, zoo_lm.jittered),
          (MATRICES, zoo_lm.scaled(6.0)),
          (RESIDUAL_WRITES, zoo_lm.scaled(40.0))]


def harness(**more):
    return zoo_lm.ZooLM(
        "kimi_linear", tiny={**TINY, **more}, reference=reference, driver=driver,
        departures=departures, seq=SEQ, mutable=("router_state", "kda"), training=True,
        lively=LIVELY,
        # the check's cases run two layers: KDA + dense, latent + sparse
        short={"num_hidden_layers": 2, "kda_layers": "1", "full_attn_layers": "2"})


lm = harness()
# a selection bias that is not zero
BIAS = jnp.asarray(np.random.default_rng(2).normal(size=(4, 16)) * 0.02, jnp.float32)


def zoo():
    return lm.zoo


def cfg_of(**more):
    return zoo().custom_model(**lm.tiny_params(**more)).cfg


def collections(bias, kda_layers=4):
    zeros = jnp.zeros((bias.shape[0],), jnp.int32)
    per_layer = jnp.zeros((kda_layers,), jnp.float32)
    return {"router_state": {"e_score_correction_bias": bias, "held_passes": zeros,
                             "held_row_tiles": zeros, "held_row_chunks": zeros},
            "kda": {"chunks": jnp.zeros((), jnp.int32), "log_decay_min": per_layer,
                    "beta_mean": per_layer, "state_rms": per_layer}}


@pytest.fixture(scope="module")
def gradients():
    """(program's, reference's) loss terms and gradients of one batch from
    the same lively parameters and a selection bias that is not zero."""
    return lm.gradients(
        lambda p, batch, hp: reference.loss_terms(p, batch, hp, None, BIAS)[:2],
        collections(BIAS))


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
    assert check_lm._rel_l2(np.asarray(got[leaf]), np.asarray(want[leaf])) < 2e-4


def test_every_leaf_of_the_model_is_compared(gradients):
    (_, got), _ = gradients
    assert set(got) == set(LEAVES) and len(LEAVES) == 37


def random_mixer(seed=8, c=48, heads=4, d=16, width=4):
    r = np.random.default_rng(seed)
    wide = heads * d
    shapes = {"kda_norm": (c,), "kda_wq": (c, wide), "kda_wk": (c, wide), "kda_wv": (c, wide),
              "kda_conv_q": (width, wide), "kda_conv_k": (width, wide),
              "kda_conv_v": (width, wide), "kda_f_a": (c, d), "kda_f_b": (d, wide),
              "kda_A_log": (heads,), "kda_dt_bias": (wide,), "kda_beta": (c, heads),
              "kda_g_a": (c, d), "kda_g_b": (d, wide), "kda_onorm": (d,), "kda_wo": (wide, c)}
    p = {k: jnp.asarray(r.uniform(0.5, 1.5, s) if k.endswith("norm")
                        else r.normal(size=s) * 0.5, jnp.float32) for k, s in shapes.items()}
    return p, jnp.asarray(r.normal(size=(2, 37, c)), jnp.float32)


@pytest.mark.parametrize("route", ["plain", "kernel"])
def test_the_counters_say_which_route_walked_the_chunks(monkeypatch, route):
    """`kda/kernel_chunks` beside `kda/chunks` at lane-wide linear heads (2 of
    128): none on the CPU's plain route, all of them where the Pallas kernels
    run (interpret mode here), and the step's loss the same by both."""
    from elasticdl_tpu.ops import pallas_attention
    if route == "kernel":
        # the signal alone: a kernel under `jax.checkpoint` cannot run inside
        # the TPU interpreter's context
        monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
    wide = {"linear_num_heads": 2, "linear_head_dim": 128}
    spec, trainer = lm.fresh_trainer(warmup_steps=1, **wide, **lm.short)
    data = lm.batches(steps=1)[0]
    state, logs = trainer.train_step(trainer.init_state(data), data)
    kda = state.extra_vars["kda"]
    # 1 KDA layer x 2 sequences x 2 heads x ceil(40 / 16) chunks
    assert int(kda["chunks"]) == 2 * 2 * 3
    assert int(kda["kernel_chunks"]) == (2 * 2 * 3 if route == "kernel" else 0)
    _LOSS_BY_ROUTE[route] = float(logs["loss"])
    if len(_LOSS_BY_ROUTE) == 2:
        assert _LOSS_BY_ROUTE["kernel"] == pytest.approx(_LOSS_BY_ROUTE["plain"], rel=1e-5)


_LOSS_BY_ROUTE = {}


# (interpret mode, tokens) -> the convolutions that took the kernels, the
# chunks the delta rule's kernels walked (2 sequences x 2 heads x chunks of 16)
CONV_ROUTES = {"plain": (False, 64, 0, 0), "kernel": (True, 64, 3, 2 * 2 * 4),
               "ragged_tokens": (True, 40, 0, 2 * 2 * 3)}


@pytest.mark.parametrize("route", sorted(CONV_ROUTES))
def test_the_counter_says_which_route_the_convolutions_took(monkeypatch, route):
    """`kda/kernel_convs` at planes of whole lanes (2 heads of 128): q's, k's
    and v's of the one KDA layer where the Pallas kernels run (interpret mode
    here) and the tokens are whole time blocks; none on the CPU's plain route,
    and none at 40 tokens, where the delta rule still takes ITS kernels; the
    step's loss the same by both routes."""
    from elasticdl_tpu.ops import pallas_attention
    interpret, seq, convs, chunks = CONV_ROUTES[route]
    if interpret:
        monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
    wide = {"linear_num_heads": 2, "linear_head_dim": 128}
    spec, trainer = lm.fresh_trainer(warmup_steps=1, **wide, **lm.short)
    data = lm.batches(steps=1, seq=seq)[0]
    state, logs = trainer.train_step(trainer.init_state(data), data)
    kda = state.extra_vars["kda"]
    assert int(kda["kernel_convs"]) == convs
    assert int(kda["kernel_chunks"]) == chunks
    if seq == 64:
        _CONV_LOSS_BY_ROUTE[route] = float(logs["loss"])
    if len(_CONV_LOSS_BY_ROUTE) == 2:
        assert _CONV_LOSS_BY_ROUTE["kernel"] == pytest.approx(
            _CONV_LOSS_BY_ROUTE["plain"], rel=1e-5)


_CONV_LOSS_BY_ROUTE = {}


def test_the_mixer_alone_matches_the_reference_s_token_by_token():
    m, cfg = zoo(), cfg_of()
    p, x = random_mixer()
    hp = reference.hyper(lm.tiny_params())
    with jax.default_matmul_precision("highest"):
        got, stats = m.kda(p, x, cfg)
        want = reference.kda(p, x, hp)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert stats.shape == (2, 3)
    log_decay_min, beta_mean, state_rms = np.asarray(stats).T
    assert np.all(log_decay_min < 0) and np.all((0 < beta_mean) & (beta_mean < 1))
    assert np.all(state_rms > 0)


def test_the_decay_is_one_number_a_channel_and_the_write_strength_one_a_head():
    m = zoo()
    p, _ = random_mixer()
    a = jnp.asarray(np.random.default_rng(1).normal(size=(2, 5, 64)), jnp.float32)
    g = m.log_decay(p, a, 4)
    assert g.shape == (2, 5, 4, 16) and float(jnp.max(g)) < 0
    want = -np.exp(np.asarray(p["kda_A_log"]))[:, None] * np.log1p(np.exp(
        np.asarray(a + p["kda_dt_bias"]).reshape(2, 5, 4, 16)))
    np.testing.assert_allclose(g, want, rtol=1e-5)
    assert float(jnp.std(g[0, 0, 0])) > 0                       # channels differ
    q, k = m.qk_normalised(jnp.ones((1, 1, 1, 16)) * 3.0, jnp.ones((1, 1, 1, 16)) * 5.0)
    np.testing.assert_allclose(jnp.sum(k * k), 1.0, rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(q * q), 1.0 / 16, rtol=1e-5)


# ------------------------------------------------------------------ #
# the layers' kinds, from the published lists


def test_kinds_and_feed_forwards_follow_the_published_lists():
    cfg = zoo().Config()                   # the published keys
    kinds = [cfg.kind(l) for l in range(1, 28)]
    assert kinds[:5] == ["kda", "kda", "kda", "mla", "kda"]
    assert [l for l in range(1, 28) if cfg.kind(l) == "mla"] == [4, 8, 12, 16, 20, 24, 27]
    assert cfg.kind(27) == "mla" and cfg.kind(26) == "kda" and cfg.kind(25) == "kda"
    assert cfg.is_dense(1) and not cfg.is_dense(2)
    assert (cfg.layers_of("kda"), cfg.layers_of("mla"), cfg.sparse_layers) == (20, 7, 26)
    cut = cfg_of()
    assert (cut.layers_of("kda"), cut.layers_of("mla"), cut.sparse_layers) == (4, 1, 4)
    assert (cut.num_experts, cut.held, cut.num_experts_per_tok) == (16, (4, 4), 3)


@pytest.mark.parametrize("lists, match", [
    ({"kda_layers": "1,2,3,4", "full_attn_layers": "4"}, "layer 4 of 5 is in both"),
    ({"kda_layers": "1,2,3", "full_attn_layers": "4"}, "layer 5 of 5 is in neither")])
def test_a_layer_is_of_exactly_one_kind(lists, match):
    with pytest.raises(ValueError, match=match):
        zoo().custom_model(**lm.tiny_params(**lists))


def test_the_parameters_are_stacked_by_kind():
    shapes = jax.tree_util.tree_map(lambda a: a.shape, dict(lm.params()))
    assert shapes["kda_wq"] == (4, 48, 64) and shapes["kda_conv_k"] == (4, 4, 64)
    assert shapes["kda_f_a"] == (4, 48, 16) and shapes["kda_g_b"] == (4, 16, 64)
    assert shapes["kda_A_log"] == (4, 4) and shapes["kda_dt_bias"] == (4, 64)
    assert shapes["kda_beta"] == (4, 48, 4) and shapes["kda_onorm"] == (4, 16)
    assert shapes["q_proj"] == (1, 48, 4 * 24) and shapes["kv_b"] == (1, 16, 4 * 32)
    assert shapes["mlp_gate"] == (1, 48, 96) and shapes["w_gate"] == (4, 4, 48, 24)
    assert shapes["moe_router"] == (4, 48, 16)
    assert "q_a" not in shapes and "q_a_norm" not in shapes


# ------------------------------------------------------------------ #
# latent attention: one projection for the query, no positions


def latent_params(seed=10, c=48, heads=4):
    r = np.random.default_rng(seed)
    shapes = {"attn_norm": (c,), "q_proj": (c, heads * 24), "kv_a": (c, 16 + 8),
              "kv_a_norm": (16,), "kv_b": (16, heads * (16 + 16)), "wo": (heads * 16, c)}
    return {k: jnp.asarray(r.uniform(0.5, 1.5, s) if k.endswith("norm")
                           else r.normal(size=s) * 0.4, jnp.float32)
            for k, s in shapes.items()}


def test_latent_attention_without_a_low_rank_query_matches_the_reference_s():
    m, cfg = zoo(), cfg_of()
    p = latent_params()
    x = jnp.asarray(np.random.default_rng(11).normal(size=(2, 21, 48)), jnp.float32)
    hp = reference.hyper(lm.tiny_params())
    with jax.default_matmul_precision("highest"):
        got = m.glm.latent_attention(p, x, cfg, rotate=m.no_positions)
        np.testing.assert_allclose(got, reference.attention(p, x, hp), rtol=2e-4, atol=2e-5)


def test_the_latent_layer_sees_positions_through_its_mask_alone():
    """No rotation of anything: the last position, which sees every key, reads
    the same whatever ORDER the positions before it come in; under a rotary
    table (the departure) it does not."""
    m, cfg = zoo(), cfg_of()
    p = latent_params()
    r = np.random.default_rng(12)
    x = jnp.asarray(r.normal(size=(1, 21, 48)), jnp.float32)
    shuffled = x.at[:, :20].set(x[:, r.permutation(20)])
    with jax.default_matmul_precision("highest"):
        last = lambda x, rotate: m.glm.latent_attention(p, x, cfg, rotate=rotate)[:, -1]
        np.testing.assert_allclose(last(shuffled, m.no_positions), last(x, m.no_positions),
                                   rtol=1e-4, atol=1e-5)
        rotary = lambda part: m.glm.rope(part, 10000.0)
        assert float(jnp.max(jnp.abs(last(shuffled, rotary) - last(x, rotary)))) > 1e-3


# ------------------------------------------------------------------ #
# the share of a deployment, tied to the whole (model-configs guide §4)


def test_four_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """One sparse feed-forward at 16 experts top-3: the routed parts that 4
    shares of 4 experts compute (the program's held dispatch, the shared
    expert taken away) plus the shared expert ONCE equal what the reference
    gives for the layer with every expert held — as the cell's 32 shares of 8
    make its 256."""
    m = zoo().glm
    r = np.random.default_rng(3)
    c, f, e = 48, 24, 16
    normal = lambda *shape: r.normal(size=shape) * 0.2
    whole = {"moe_norm": r.uniform(0.5, 1.5, (c,)), "moe_router": r.normal(size=(c, e)),
             "shared_gate": normal(c, f), "shared_up": normal(c, f),
             "shared_down": normal(f, c), "w_gate": normal(e, c, f),
             "w_up": normal(e, c, f), "w_down": normal(e, f, c)}
    whole = {k: jnp.asarray(v, jnp.float32) for k, v in whole.items()}
    x = jnp.asarray(r.normal(size=(2, 9, c)), jnp.float32)
    bias = jnp.asarray(r.normal(size=(e,)) * 0.05, jnp.float32)
    hp_whole = reference.hyper(lm.tiny_params(num_experts=16, first_expert=0))
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p, x: reference.moe(p, x, bias, None, hp_whole))(whole, x)
        shared = m.gated_mlp(
            m.rmsnorm(x, whole["moe_norm"], 1e-5).reshape(-1, c), whole["shared_gate"],
            whole["shared_up"], whole["shared_down"], jnp.float32).reshape(x.shape)
        total = shared
        for share in range(4):
            cfg = cfg_of(num_experts=4, first_expert=4 * share)
            held = slice(4 * share, 4 * share + 4)
            p = {**whole, **{k: whole[k][held] for k in ("w_gate", "w_up", "w_down")}}
            y, stats = m.moe(p, x, bias, cfg)
            total = total + (y - shared)
            assert stats["expert_idx"].shape == (18, 3)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)
    assert float(jnp.max(jnp.abs(want - shared))) > 1e-2
