"""LFM2-MoE (model_zoo/transformer/lfm2_moe.py: a double-gated short
convolution of K = 3 as the mixer of three layers in four, grouped-query
attention with head norms and rotary positions in the fourth, a leading dense
layer, held SwiGLU experts behind a sigmoid router with a selection bias, a
tied head) against its plain reference (benchmark/reference/lfm2_moe.py) on
seeded weights, at a tiny size on the CPU: hidden 64, published layers 0, 2, 3
of a four-entry `layer_types` (a dense convolution layer, a sparse attention
layer, a sparse convolution layer), 4 heads of 16 on 2 key-value heads, 8
experts top-2 of which experts 2-3 are held, vocabulary 256, float32.

The benchmark's own comparison, and the departures it must catch, are in
`tests/test_lfm2_moe_check.py`; the mixer's operation on both of its routes in
`tests/test_gated_short_conv.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_lm, common
from tests import zoo_lm

TINY = zoo_lm.preset("tiny-lm-lfm2.json")
SEQ = 36
NORMS = ("embedding_norm", "operator_norm", "ffn_norm", "q_norm", "k_norm")
MATRICES = ("conv_in", "conv_out", "wq", "wk", "wv", "wo", "mlp_gate", "mlp_up", "mlp_down",
            "w_gate", "w_up", "w_down")
LEAVES = ("embed", "moe_router", "conv_w") + NORMS + MATRICES

reference = common.load_module("reference", "lfm2_moe")
flops = common.load_module("flops", "lfm2_moe")
driver = common.load_module("drivers", "resident_lm_model")
departures = common.load_module("rehearse", "departures_lfm2_moe")

# router logits of order one, every norm's weight away from one, projections
# large enough that the gates differ from token to token and attention is far
# from a running mean
LIVELY = [(("moe_router",), zoo_lm.scaled(15.0)),
          (NORMS, zoo_lm.jittered),
          (MATRICES, zoo_lm.scaled(6.0)),
          (("embed",), zoo_lm.scaled(20.0))]

lm = zoo_lm.ZooLM("lfm2_moe", tiny=TINY, reference=reference, driver=driver,
                  departures=departures, seq=SEQ, mutable=("router_state", "conv", "attn"),
                  training=True, lively=LIVELY,
                  # the check's cases run the two sparse layers, one of each mixer
                  short={"num_hidden_layers": 2, "kept_layers": "2,3"})
# a selection bias that is not zero
BIAS = jnp.asarray(np.random.default_rng(2).normal(size=(2, 8)) * 0.02, jnp.float32)


def zoo():
    return lm.zoo


def cfg_of(**more):
    return zoo().custom_model(**lm.tiny_params(**more)).cfg


def collections(bias):
    zeros = jnp.zeros((bias.shape[0],), jnp.int32)
    return {"router_state": {"expert_bias": bias, "held_passes": zeros,
                             "held_row_tiles": zeros, "held_row_chunks": zeros,
                             "pairs_held_share": jnp.zeros((bias.shape[0],), jnp.float32)},
            "conv": {"kernel_convs": jnp.zeros((), jnp.int32)},
            "attn": {"kv_block_visits": jnp.zeros((), jnp.int32)}}


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
    assert set(got) == set(LEAVES) and len(LEAVES) == 20


def test_the_routing_and_the_bias_update_match_the_reference():
    """The program's own choice under a bias that is not zero is the
    reference's, pair for pair, and so is the bias it leaves."""
    batch = lm.batches(steps=1)[0]
    hp = reference.hyper(lm.tiny_params())
    idx, weights, router_input = lm.assignments()(lm.params(), BIAS, batch["features"])
    with jax.default_matmul_precision("highest"):
        chosen, probs = jax.jit(lambda p, x: reference.routers_on(p, x, hp, BIAS))(
            lm.params(), router_input)
    figures = check_lm.routing_figures(idx, weights, chosen, probs)
    assert figures["agreement"] == 1.0 and figures["weight_rel_median"] < 1e-6
    np.testing.assert_allclose(
        zoo().updated_bias(BIAS, idx, cfg_of()),
        reference.bias_update(BIAS, jnp.asarray(check_lm.chosen_mask(idx, 8))), atol=1e-9)


# ------------------------------------------------------------------ #
# the layers' kinds, from the published list


def test_the_kind_of_a_layer_is_looked_up_by_its_published_index():
    cfg = zoo().Config()                   # the published keys
    attention = [i for i in range(24) if cfg.kind(i) == "full_attention"]
    assert attention == [2, 6, 10, 14, 18, 21]
    # not periodic at its end: a period of four from 2 would put one at 22
    assert cfg.kind(21) == "full_attention" and cfg.kind(22) == "conv"
    assert cfg.is_dense(0) and cfg.is_dense(1) and not cfg.is_dense(2)
    assert (cfg.layers_of("conv"), cfg.layers_of("full_attention")) == (18, 6)
    assert (cfg.dense_layers, cfg.sparse_layers, cfg.head_dim) == (2, 22, 64)
    cut = zoo().custom_model(kept_layers="0,2,3,4,5", num_hidden_layers=5, num_experts=8,
                             router_experts=32).cfg
    assert [cut.kind(l) for l in cut.layers] == ["conv", "full_attention", "conv", "conv", "conv"]
    assert (cut.dense_layers, cut.sparse_layers, cut.held, cut.num_experts) == (1, 4, (0, 8), 32)
    tiny = cfg_of()
    assert [tiny.kind(l) for l in tiny.layers] == ["conv", "full_attention", "conv"]


@pytest.mark.parametrize("params, match", [
    ({"kept_layers": "0,2"}, "does not list 3 published layers"),
    ({"kept_layers": "0,3,2"}, "does not list 3 published layers in order"),
    ({"kept_layers": "0,2,4"}, "beyond the 4 entries of layer_types"),
    ({"layer_types": "conv,conv,sliding_attention,conv"}, "a layer is one of"),
    ({"num_key_value_heads": 3}, "do not divide over 3 key-value heads")])
def test_a_configuration_that_cannot_be_built_is_refused(params, match):
    with pytest.raises(ValueError, match=match):
        zoo().custom_model(**lm.tiny_params(**params))


def test_the_parameters_are_stacked_by_kind():
    shapes = jax.tree_util.tree_map(lambda a: a.shape, dict(lm.params()))
    assert shapes["operator_norm"] == shapes["ffn_norm"] == (3, 64)
    assert shapes["conv_in"] == (2, 64, 192) and shapes["conv_w"] == (2, 3, 64)
    assert shapes["conv_out"] == (2, 64, 64)
    assert shapes["wq"] == (1, 64, 64) and shapes["wk"] == shapes["wv"] == (1, 64, 32)
    assert shapes["q_norm"] == shapes["k_norm"] == (1, 16)
    assert shapes["mlp_gate"] == (1, 64, 96) and shapes["w_gate"] == (2, 2, 64, 24)
    assert shapes["moe_router"] == (2, 64, 8)
    assert "head" not in shapes


def test_the_shape_functions_count_the_cut_and_the_published_model():
    cell = common.model_params(common.load_json("configs", "lfm2-8b-a1b.json"))
    assert flops.parameter_count(cell) == 507_820_160
    published = {**cell, "num_hidden_layers": "24", "kept_layers": "", "num_experts": "32",
                 "vocab_size": "65536"}
    assert flops.parameter_count(published) == 8_339_929_856
    assert flops.active_parameter_count(published) == 1_423_422_208
    # and the program's own leaves add up to the same, at the tiny size
    built = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(lm.params()))
    assert built == flops.parameter_count(lm.tiny_params())


# ------------------------------------------------------------------ #
# one matrix is embedding and head


def test_the_tied_leaf_s_gradient_is_the_sum_of_both_uses():
    """The loss's gradient in `embed` is what the lookup sends back plus what
    the head's matmul does: with the head reading a COPY, the two parts come
    apart, neither is zero, and they add up to the tied gradient."""
    m, cfg = zoo(), cfg_of()
    params, batch = dict(lm.params()), lm.batches(steps=1)[0]

    def split_loss(params, head_matrix):
        outputs, _ = m.forward(params, BIAS, batch["features"], cfg)
        assert outputs["embed"] is params["embed"]          # ONE matrix
        return jnp.mean(m.loss(batch["labels"], {**outputs, "embed": head_matrix})["loss"])

    loss_of = lambda params: split_loss(params, params["embed"])

    with jax.default_matmul_precision("highest"):
        tied = jax.jit(jax.grad(loss_of))(params)["embed"]
        lookup, head = jax.jit(jax.grad(split_loss, argnums=(0, 1)))(params, params["embed"])
    assert float(jnp.linalg.norm(lookup["embed"])) > 0 and float(jnp.linalg.norm(head)) > 0
    np.testing.assert_allclose(tied, lookup["embed"] + head, rtol=1e-4, atol=1e-7)


def test_the_loss_in_row_blocks_is_the_whole_logits_(monkeypatch):
    """`cross_entropy` at blocks of 8 positions (37 leave a ragged last block)
    against the cross entropy of the logits made whole: values and both
    gradients."""
    import optax

    m = zoo()
    r = np.random.default_rng(4)
    hidden = jnp.asarray(r.normal(size=(2, 37, 64)), jnp.float32)
    embed = jnp.asarray(r.normal(size=(256, 64)) * 0.3, jnp.float32)
    labels = jnp.asarray(r.integers(0, 256, (2, 37)), jnp.int32)
    whole = lambda h, e: optax.softmax_cross_entropy_with_integer_labels(
        m.logits_of({"hidden": h, "embed": e}), labels)
    monkeypatch.setattr(m, "HEAD_ROWS", 8)
    blocked = lambda h, e: m.cross_entropy(h, e, labels)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(blocked(hidden, embed), whole(hidden, embed),
                                   rtol=1e-5, atol=1e-5)
        got = jax.grad(lambda h, e: jnp.sum(blocked(h, e)), argnums=(0, 1))(hidden, embed)
        want = jax.grad(lambda h, e: jnp.sum(whole(h, e)), argnums=(0, 1))(hidden, embed)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------------ #
# the share of a deployment, tied to the whole (model-configs guide §4)


def test_four_shares_make_the_uncut_layer():
    """One sparse feed-forward at 8 experts top-2: the parts that 4 shares of
    2 experts compute (the program's held dispatch; the model has no shared
    expert) add up to what the reference gives for the layer with every
    expert held — as the cell's four shares of 8 make its 32."""
    m = zoo()
    r = np.random.default_rng(3)
    c, f, e = 64, 24, 8
    normal = lambda *shape: r.normal(size=shape) * 0.2
    whole = {"ffn_norm": r.uniform(0.5, 1.5, (c,)), "moe_router": r.normal(size=(c, e)),
             "w_gate": normal(e, c, f), "w_up": normal(e, c, f), "w_down": normal(e, f, c)}
    whole = {k: jnp.asarray(v, jnp.float32) for k, v in whole.items()}
    x = jnp.asarray(r.normal(size=(2, 9, c)), jnp.float32)
    bias = jnp.asarray(r.normal(size=(e,)) * 0.05, jnp.float32)
    hp_whole = reference.hyper(lm.tiny_params(num_experts=8, first_expert=0))
    with jax.default_matmul_precision("highest"):
        want, _, _ = jax.jit(lambda p, x: reference.moe(p, x, bias, None, hp_whole))(whole, x)
        total = 0.0
        for share in range(4):
            cfg = cfg_of(num_experts=2, first_expert=2 * share)
            held = slice(2 * share, 2 * share + 2)
            p = {**whole, **{k: whole[k][held] for k in ("w_gate", "w_up", "w_down")}}
            y, stats = m.moe(p, x, bias, cfg)
            total = total + y
            assert stats["expert_idx"].shape == (18, 2)
    assert float(jnp.max(jnp.abs(want))) > 0.01
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


# ------------------------------------------------------------------ #
# the mixers alone


def test_the_convolution_mixer_alone_matches_the_reference_s():
    m, cfg = zoo(), cfg_of()
    r = np.random.default_rng(8)
    p = {"operator_norm": r.uniform(0.5, 1.5, (64,)), "conv_in": r.normal(size=(64, 192)) * 0.3,
         "conv_w": r.uniform(-0.6, 0.6, (3, 64)), "conv_out": r.normal(size=(64, 64)) * 0.3}
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    x = jnp.asarray(r.normal(size=(2, 37, 64)), jnp.float32)
    hp = reference.hyper(lm.tiny_params())
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(m.short_conv(p, x, cfg), reference.short_conv(p, x, hp),
                                   rtol=2e-4, atol=2e-5)
        # causal: a later token changes nothing before it
        later = x.at[:, 20:].add(1.0)
        np.testing.assert_array_equal(m.short_conv(p, later, cfg)[:, :20],
                                      m.short_conv(p, x, cfg)[:, :20])


def test_the_attention_mixer_alone_matches_the_reference_s():
    m, cfg = zoo(), cfg_of()
    r = np.random.default_rng(9)
    shapes = {"operator_norm": (64,), "wq": (64, 64), "wk": (64, 32), "wv": (64, 32),
              "wo": (64, 64), "q_norm": (16,), "k_norm": (16,)}
    p = {k: jnp.asarray(r.uniform(0.5, 1.5, s) if k.endswith("norm")
                        else r.normal(size=s) * 0.4, jnp.float32) for k, s in shapes.items()}
    x = jnp.asarray(r.normal(size=(2, 21, 64)), jnp.float32)
    hp = reference.hyper(lm.tiny_params())
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(m.attention(p, x, cfg), reference.attention(p, x, hp),
                                   rtol=2e-4, atol=2e-5)


def test_a_training_step_counts_its_routes_and_its_held_share():
    spec, trainer = lm.trainer()
    data = lm.batches(steps=1)[0]
    state, logs = trainer.train_step(lm.state(), data)
    router, cfg = state.extra_vars["router_state"], spec.model.cfg
    share = np.asarray(router["pairs_held_share"])
    assert share.shape == (2,) and np.all((0 < share) & (share < 1))
    assert np.asarray(router["held_passes"]).tolist() == [1, 1]
    assert float(np.max(np.abs(router["expert_bias"]))) == pytest.approx(1e-3)
    # the CPU has no kernel: the plain route, and no flash grid to count
    assert int(state.extra_vars["conv"]["kernel_convs"]) == 0
    assert int(state.extra_vars["attn"]["kv_block_visits"]) == zoo().kv_block_visits(cfg, SEQ)
    assert set(logs) >= {"loss", "loss_ce"}
