"""Qwen3-Next (model_zoo/transformer/qwen3_next.py: a Gated DeltaNet — a delta
rule with ONE decay a head, key heads read by pairs of value heads — as the
mixer of three layers in four, a gated softmax attention with head norms and a
quarter of each head rotated in the fourth, held SiLU experts behind a softmax
router beside a sigmoid-gated shared expert, an untied head in row blocks)
against its plain reference (benchmark/reference/qwen3_next.py) on seeded
weights, at a tiny size on the CPU: hidden 64, published layers 2, 3, 4 at an
interval of four (Gated DeltaNet, attention, Gated DeltaNet), 2 key heads read
by 4 value heads of 16, 4 query heads of 16 on 2 key-value heads, 16 experts
top-3 of which experts 4-7 are held, vocabulary 256, float32.

The benchmark's own comparison, and the departures it must catch, are in
`tests/test_qwen3_next_check.py`; the recurrence on both of its routes in
`tests/test_delta_rule_scalar.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_lm, common
from tests import zoo_lm

TINY = zoo_lm.preset("tiny-lm-gdn.json")
SEQ = 36
ZERO_NORMS = ("final_norm", "mixer_norm", "moe_norm", "q_norm", "k_norm")
MATRICES = ("gdn_qkvz", "gdn_wo", "wq", "wk", "wv", "wo", "shared_gate", "shared_up",
            "shared_down", "w_gate", "w_up", "w_down", "head")
SMALL = ("gdn_ba", "gdn_conv", "shared_expert_gate")
LEAVES = (("embed", "moe_router", "gdn_onorm", "gdn_A_log", "gdn_dt_bias")
          + ZERO_NORMS + MATRICES + SMALL)

reference = common.load_module("reference", "qwen3_next")
flops = common.load_module("flops", "qwen3_next")
driver = common.load_module("drivers", "resident_lm_stateless")
departures = common.load_module("rehearse", "departures_qwen3_next")

# router logits of order one, every norm's weight away from its start (the
# (1 + w) norms start at ZERO: drawn, not scaled), projections large enough
# that the gates differ from token to token and attention is far from a
# running mean
LIVELY = [(("moe_router",), zoo_lm.scaled(15.0)),
          (ZERO_NORMS, zoo_lm.drawn(0.3)),
          (("gdn_onorm",), zoo_lm.jittered),
          (MATRICES, zoo_lm.scaled(6.0)),
          (SMALL, zoo_lm.scaled(10.0)),
          (("embed",), zoo_lm.scaled(20.0))]
MUTABLE = ("router_state", "gdn", "attn", "losses")

lm = zoo_lm.ZooLM("qwen3_next", tiny=TINY, reference=reference, driver=driver,
                  departures=departures, seq=SEQ, mutable=MUTABLE, training=True,
                  lively=LIVELY, sown={"loss_aux": "load_balance"},
                  # the check's cases run one layer of each mixer
                  short={"num_hidden_layers": 2, "kept_layers": "2,3"})


def zoo():
    return lm.zoo


def cfg_of(**more):
    return zoo().custom_model(**lm.tiny_params(**more)).cfg


@pytest.fixture(scope="module")
def gradients():
    """(program's, reference's) loss terms and gradients of one batch from
    the same lively parameters."""
    return lm.gradients(lambda p, batch, hp: reference.loss_terms(p, batch, hp)[:2])


# ------------------------------------------------------------------ #
# the model against the reference


@pytest.mark.parametrize("term", ["loss", "loss_ce", "loss_aux"])
def test_loss_terms_match_reference(gradients, term):
    ((total, got), _), ((ref_total, want), _) = gradients
    got, want = ({**got, "loss": total}[term], {**want, "loss": ref_total}[term])
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    if term != "loss_aux":
        assert float(want) > np.log(TINY["vocab_size"]) - 0.5          # untrained


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_reference(gradients, leaf):
    (_, got), (_, want) = gradients
    assert got[leaf].shape == want[leaf].shape
    assert np.linalg.norm(want[leaf]) > 0
    assert check_lm._rel_l2(np.asarray(got[leaf]), np.asarray(want[leaf])) < 2e-4


def test_every_leaf_of_the_model_is_compared(gradients):
    (_, got), _ = gradients
    assert set(got) == set(LEAVES) and len(LEAVES) == 26


def test_the_routing_matches_the_reference_pair_for_pair():
    batch = lm.batches(steps=1)[0]
    hp = reference.hyper(lm.tiny_params())
    idx, weights, router_input = lm.assignments()(lm.params(), batch["features"])
    assert idx.shape == (3, 2 * SEQ, 3) and router_input.shape == (3, 2, SEQ, 64)
    with jax.default_matmul_precision("highest"):
        chosen, probs = jax.jit(lambda p, x: reference.routers_on(p, x, hp))(
            lm.params(), router_input)
    figures = check_lm.routing_figures(idx, weights, chosen, probs)
    assert figures["agreement"] == 1.0 and figures["weight_rel_median"] < 1e-6
    np.testing.assert_allclose(np.sum(weights, axis=-1), 1.0, atol=1e-6)   # renormalised


# ------------------------------------------------------------------ #
# the layers' kinds, at their published indices


def test_the_kind_of_a_layer_is_that_of_its_published_index():
    cfg = zoo().Config()                   # the published keys
    attention = [i for i in range(48) if cfg.kind(i) == "full_attention"]
    assert attention == list(range(3, 48, 4))
    assert (cfg.layers_of("linear_attention"), cfg.layers_of("full_attention")) == (36, 12)
    assert (cfg.rotary_dim, cfg.value_group, cfg.key_width, cfg.value_width) == (64, 2, 2048, 4096)
    cut = zoo().custom_model(kept_layers="0,1,2,3", num_hidden_layers=4, num_experts=32,
                             router_experts=512).cfg
    assert [cut.kind(l) for l in cut.layers] == ["linear_attention"] * 3 + ["full_attention"]
    assert (cut.held, cut.num_experts) == ((0, 32), 512)
    tiny = cfg_of()
    assert [tiny.kind(l) for l in tiny.layers] == [
        "linear_attention", "full_attention", "linear_attention"]
    # a published list, where one is given, is looked up and not computed
    listed = cfg_of(layer_types="full_attention,linear_attention,linear_attention,"
                                "linear_attention,full_attention")
    assert [listed.kind(l) for l in listed.layers] == [
        "linear_attention", "linear_attention", "full_attention"]


@pytest.mark.parametrize("params, match", [
    ({"kept_layers": "0,2"}, "does not list 3 published layers"),
    ({"kept_layers": "0,3,2"}, "does not list 3 published layers in order"),
    ({"layer_types": "linear_attention,full_attention"}, "beyond the 2 entries of layer_types"),
    ({"layer_types": "a,b,conv,linear_attention,full_attention"}, "a layer is one of"),
    ({"linear_num_value_heads": 3}, "do not divide over 2 key heads"),
    ({"num_key_value_heads": 3}, "do not divide over 3 key-value heads"),
    ({"partial_rotary_factor": 0.2}, "rotates 3 of 16")])
def test_a_configuration_that_cannot_be_built_is_refused(params, match):
    with pytest.raises(ValueError, match=match):
        zoo().custom_model(**lm.tiny_params(**params))


def test_the_parameters_are_stacked_by_kind():
    shapes = jax.tree_util.tree_map(lambda a: a.shape, dict(lm.params()))
    assert shapes["mixer_norm"] == shapes["moe_norm"] == (3, 64)
    assert shapes["gdn_qkvz"] == (2, 64, 192) and shapes["gdn_ba"] == (2, 64, 8)
    assert shapes["gdn_conv"] == (2, 4, 128) and shapes["gdn_wo"] == (2, 64, 64)
    assert shapes["gdn_A_log"] == shapes["gdn_dt_bias"] == (2, 4)
    assert shapes["gdn_onorm"] == (2, 16)
    assert shapes["wq"] == (1, 64, 128) and shapes["wk"] == shapes["wv"] == (1, 64, 32)
    assert shapes["q_norm"] == shapes["k_norm"] == (1, 16)
    assert shapes["w_gate"] == (3, 4, 64, 24) and shapes["moe_router"] == (3, 64, 16)
    assert shapes["shared_expert_gate"] == (3, 64, 1)
    assert shapes["head"] == shapes["embed"] == (256, 64)             # untied, one layout
    assert not any("mtp" in name for name in shapes)                  # no MTP module


def test_the_shape_functions_count_the_cut_and_the_published_model():
    cell = common.model_params(common.load_json("configs", "qwen3-next-80b-a3b.json"))
    assert flops.parameter_count(cell) == 625_667_136
    assert flops.parameter_count(cell, published=True) == 79_674_391_296
    assert flops.active_parameter_count(cell, published=True) == 3_563_764_480
    # and the program's own leaves add up to the same, at the tiny size
    built = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(lm.params()))
    assert built == flops.parameter_count(lm.tiny_params())
    shape = flops.shape(cell, 1, 16384)
    # q and k at 16 heads, v and o at 32, g and beta as (T, 32)
    assert shape["delta_rule_bytes_per_step"] == 4 * 16384 * 3 * (6 * 2048 + 5 * 4096 + 6 * 32)
    assert 0.76e12 < shape["delta_rule_flops_per_step"] < 0.78e12
    assert 26.0e12 < shape["model_flops_per_sample"] < 26.5e12


def test_the_seeded_norms_start_at_zero_and_the_decay_is_finite():
    spec, trainer = lm.trainer()
    seeded = trainer.init_state(lm.batches(steps=1)[0]).params
    for name in ZERO_NORMS:
        assert float(jnp.max(jnp.abs(seeded[name]))) == 0.0
    assert float(jnp.min(seeded["gdn_onorm"])) == float(jnp.max(seeded["gdn_dt_bias"])) == 1.0
    a_log = np.asarray(seeded["gdn_A_log"])
    assert np.all(np.isfinite(a_log)) and np.all(a_log <= np.log(16.0))


# ------------------------------------------------------------------ #
# the head in row blocks


def test_the_loss_in_row_blocks_is_the_whole_logits_(monkeypatch):
    """`loss` at blocks of 8 positions (37 leave a ragged last block) against
    the cross entropy of the logits made whole: values and both gradients."""
    import optax

    from model_zoo.transformer import lfm2_moe

    m = zoo()
    r = np.random.default_rng(4)
    hidden = jnp.asarray(r.normal(size=(2, 37, 64)), jnp.float32)
    head = jnp.asarray(r.normal(size=(256, 64)) * 0.3, jnp.float32)
    labels = jnp.asarray(r.integers(0, 256, (2, 37)), jnp.int32)
    whole = lambda h, e: optax.softmax_cross_entropy_with_integer_labels(
        m.logits_of({"hidden": h, "head": e}), labels).mean(axis=-1)
    monkeypatch.setattr(lfm2_moe, "HEAD_ROWS", 8)
    blocked = lambda h, e: m.loss(labels, {"hidden": h, "head": e})["loss_ce"]
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(blocked(hidden, head), whole(hidden, head),
                                   rtol=1e-5, atol=1e-5)
        got = jax.grad(lambda h, e: jnp.sum(blocked(h, e)), argnums=(0, 1))(hidden, head)
        want = jax.grad(lambda h, e: jnp.sum(whole(h, e)), argnums=(0, 1))(hidden, head)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------------ #
# the share of a deployment, tied to the whole (model-configs guide §4)


def test_sixteen_shares_and_one_shared_expert_make_the_uncut_layer():
    """One feed-forward at 32 experts top-5: the routed parts that SIXTEEN
    shares of 2 experts compute (the program's held dispatch) plus ONE copy of
    what every chip computes alike (the gated shared expert) add up to what
    the reference gives for the layer with every expert held — as the cell's
    sixteen shares of 32 make its 512."""
    m = zoo()
    r = np.random.default_rng(3)
    c, f, e, k = 64, 24, 32, 5
    normal = lambda *shape: r.normal(size=shape) * 0.2
    whole = {"moe_norm": r.normal(size=(c,)) * 0.3, "moe_router": r.normal(size=(c, e)),
             "shared_gate": normal(c, f), "shared_up": normal(c, f), "shared_down": normal(f, c),
             "shared_expert_gate": r.normal(size=(c, 1)),
             "w_gate": normal(e, c, f), "w_up": normal(e, c, f), "w_down": normal(e, f, c)}
    whole = {name: jnp.asarray(v, jnp.float32) for name, v in whole.items()}
    x = jnp.asarray(r.normal(size=(2, 9, c)), jnp.float32)
    sizes = dict(router_experts=e, num_experts_per_tok=k)
    hp_whole = reference.hyper(lm.tiny_params(num_experts=e, first_expert=0, **sizes))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: reference.moe(p, x, None, hp_whole)[0])(whole, x)
        h = m.norm(x, whole["moe_norm"], 1e-6).reshape(-1, c)
        alike = m.shared_expert(whole, h, cfg_of(**sizes)).reshape(x.shape)
        routed = 0.0
        for share in range(16):
            cfg = cfg_of(num_experts=2, first_expert=2 * share, **sizes)
            held = slice(2 * share, 2 * share + 2)
            p = {**whole, **{name: whole[name][held] for name in ("w_gate", "w_up", "w_down")}}
            y, stats = m.moe(p, x, cfg)
            routed = routed + (y - alike)           # a share's routed part alone
            assert stats["expert_idx"].shape == (18, k)
    assert float(jnp.max(jnp.abs(want))) > 0.01 and float(jnp.max(jnp.abs(alike))) > 0.01
    np.testing.assert_allclose(routed + alike, want, rtol=2e-4, atol=2e-5)


# ------------------------------------------------------------------ #
# the mixers alone


def _mixer_params(r, shapes, ones=()):
    return {name: jnp.asarray(r.uniform(0.5, 1.5, s) if name in ones else
                              r.normal(size=s) * (0.3 if name.endswith("norm") else 0.4),
                              jnp.float32) for name, s in shapes.items()}


def test_the_gated_deltanet_mixer_alone_matches_the_reference_s():
    m, cfg = zoo(), cfg_of()
    r = np.random.default_rng(8)
    p = _mixer_params(r, {"mixer_norm": (64,), "gdn_qkvz": (64, 192), "gdn_ba": (64, 8),
                          "gdn_conv": (4, 128), "gdn_A_log": (4,), "gdn_dt_bias": (4,),
                          "gdn_onorm": (16,), "gdn_wo": (64, 64)},
                      ones=("gdn_onorm", "gdn_dt_bias"))
    x = jnp.asarray(r.normal(size=(2, 37, 64)), jnp.float32)
    hp = reference.hyper(lm.tiny_params())
    with jax.default_matmul_precision("highest"):
        got, stats = m.gated_deltanet(p, x, cfg)
        np.testing.assert_allclose(got, reference.gated_deltanet(p, x, hp),
                                   rtol=2e-4, atol=2e-5)
        # causal: a later token changes nothing before it
        later = x.at[:, 20:].add(1.0)
        np.testing.assert_allclose(m.gated_deltanet(p, later, cfg)[0][:, :20], got[:, :20],
                                   rtol=1e-6, atol=1e-6)
    assert stats.shape == (2, 3) and np.all(np.asarray(stats[:, 0]) < 0)
    assert np.all((0 < np.asarray(stats[:, 1])) & (np.asarray(stats[:, 1]) < 1))


def test_the_norm_comes_before_the_gate():
    """`gated_norm` normalises o and THEN gates; Mamba-2's order, the product
    normalised (`ops.ssm.gated_group_rmsnorm`), is another function."""
    from elasticdl_tpu.ops import ssm

    m, cfg = zoo(), cfg_of()
    r = np.random.default_rng(5)
    o, z = (jnp.asarray(r.normal(size=(2, 7, 4, 16)), jnp.float32) for _ in range(2))
    p = {"gdn_onorm": jnp.asarray(r.uniform(0.5, 1.5, (16,)), jnp.float32)}
    got = m.gated_norm(p, o, z, cfg)
    rms = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(got, rms * p["gdn_onorm"] * jax.nn.silu(z), rtol=1e-5, atol=1e-6)
    flat = lambda a: a.reshape(2, 7, 64)
    other = ssm.gated_group_rmsnorm(flat(o), flat(z), jnp.tile(p["gdn_onorm"], 4), 4, 1e-6)
    assert float(jnp.max(jnp.abs(flat(got) - other))) > 0.1


def test_the_attention_mixer_alone_matches_the_reference_s():
    m, cfg = zoo(), cfg_of()
    r = np.random.default_rng(9)
    p = _mixer_params(r, {"mixer_norm": (64,), "wq": (64, 128), "wk": (64, 32), "wv": (64, 32),
                          "wo": (64, 64), "q_norm": (16,), "k_norm": (16,)})
    x = jnp.asarray(r.normal(size=(2, 21, 64)), jnp.float32)
    hp = reference.hyper(lm.tiny_params())
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(m.attention(p, x, cfg), reference.attention(p, x, hp),
                                   rtol=2e-4, atol=2e-5)


def test_a_quarter_of_each_head_is_rotated():
    m, cfg = zoo(), cfg_of(head_dim=16)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 9, 2, 16)), jnp.float32)
    got = m.partial_rope(x, cfg)
    assert cfg.rotary_dim == 4
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])           # 12 of 16 pass
    np.testing.assert_array_equal(got[:, 0], x[:, 0])                 # position 0: no turn
    assert float(jnp.max(jnp.abs(got[:, 1:, :, :4] - x[:, 1:, :, :4]))) > 0.1
    # pairs (i, i + 2) keep their length
    length = lambda a: a[..., :2] ** 2 + a[..., 2:4] ** 2
    np.testing.assert_allclose(length(got), length(x), rtol=1e-5)
    hp = reference.hyper(lm.tiny_params())
    np.testing.assert_allclose(got, reference.rotary(x, hp), rtol=1e-5, atol=1e-6)


def test_a_training_step_counts_its_routes_and_its_held_share():
    spec, trainer = lm.trainer()
    data = lm.batches(steps=1)[0]
    state, logs = trainer.train_step(lm.state(), data)
    router, cfg = state.extra_vars["router_state"], spec.model.cfg
    share = np.asarray(router["pairs_held_share"])
    assert share.shape == (3,) and np.all((0 < share) & (share < 1))
    pairs = 2 * SEQ * TINY["num_experts_per_tok"]
    np.testing.assert_allclose(np.asarray(router["held_pairs_mean"]) * 4, share * pairs, rtol=1e-5)
    assert np.all(np.asarray(router["held_pairs_max"]) >= np.asarray(router["held_pairs_mean"]))
    assert np.asarray(router["held_experts_empty"]).shape == (3,)
    assert np.all(np.asarray(router["held_passes"]) >= 1)
    gdn = state.extra_vars["gdn"]
    # the CPU has no kernel: the plain routes
    assert int(gdn["chunks"]) == zoo().chunks_walked(cfg, 2, SEQ) == 2 * 2 * 4 * 3
    assert int(gdn["kernel_chunks"]) == int(gdn["kernel_convs"]) == 0
    assert np.asarray(gdn["log_decay_min"]).shape == (2,)
    assert int(state.extra_vars["attn"]["kv_block_visits"]) == zoo().kv_block_visits(cfg, SEQ)
    assert set(logs) >= {"loss", "loss_ce"}
    assert float(np.mean(logs["loss"])) > float(np.mean(logs["loss_ce"]))   # the sown term is added
