"""GLM-4.7-Flash (model_zoo/transformer/glm4_moe_lite.py: latent attention, a
leading dense layer, held gated-SiLU experts behind the sigmoid router, a
shared expert, one multi-token-prediction module) against its plain reference
(benchmark/reference/glm4_moe_lite.py) on seeded weights, at a tiny size on
the CPU: hidden 48, one dense and two sparse layers and the module, 4 heads of
8 + 8 (values 16), ranks 24 and 16, 16 experts top-3 of which experts 4-7 are
held, vocabulary 256, 36 tokens, float32.

The benchmark's own comparison is in `tests/test_glm4_moe_lite_tight.py`, the
departures it must catch in `tests/test_glm4_moe_lite_check.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_lm, common
from tests import zoo_lm
from tests.conftest import pallas_calls

TINY = zoo_lm.preset("tiny-lm-model.json")
LEAVES = ("embed", "final_norm", "head",
          "attn_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "wo",
          "mlp_norm", "mlp_gate", "mlp_up", "mlp_down",
          "moe_norm", "moe_router", "shared_gate", "shared_up", "shared_down",
          "w_gate", "w_up", "w_down",
          "mtp_hnorm", "mtp_enorm", "mtp_eh_proj", "mtp_final_norm")

reference = common.load_module("reference", "glm4_moe_lite")
flops = common.load_module("flops", "glm4_moe_lite")
driver = common.load_module("drivers", "resident_lm_model")
departures = common.load_module("rehearse", "departures_glm4_moe_lite")

lm = zoo_lm.ZooLM(
    "glm4_moe_lite", tiny=TINY, reference=reference, driver=driver, departures=departures,
    seq=36, mutable=("router_state",), training=True,
    # router logits of order one (as at the published width), every norm's
    # weight away from one, projections large enough that attention's softmax
    # is far from a running mean, so that positions matter
    lively=[(("moe_router",), zoo_lm.scaled(8.0)),
            (tuple(name for name in LEAVES if name.endswith("norm")), zoo_lm.jittered),
            (("q_a", "q_b", "kv_a", "kv_b", "wo", "mlp_gate", "mlp_up", "mlp_down",
              "shared_gate", "shared_up", "shared_down", "w_gate", "w_up", "w_down",
              "mtp_eh_proj"), zoo_lm.scaled(6.0))],
    # the check's cases run one dense layer, ONE sparse layer and the module:
    # every mechanism, and three blocks to trace and compile for the preset's four
    short={"num_hidden_layers": 2})
# a selection bias that is not zero
BIAS = jnp.asarray(np.random.default_rng(2).normal(size=(3, 16)) * 0.02, jnp.float32)


def zoo():
    return lm.zoo


def router_state(bias):
    zeros = jnp.zeros((bias.shape[0],), jnp.int32)
    return {"router_state": {"e_score_correction_bias": bias, "held_passes": zeros,
                             "held_row_tiles": zeros, "held_row_chunks": zeros}}


@pytest.fixture(scope="module")
def gradients():
    """(program's, reference's) loss terms and gradients of one batch from
    the same lively parameters and a selection bias that is not zero."""
    return lm.gradients(
        lambda p, batch, hp: reference.loss_terms(p, batch, hp, None, BIAS)[:2],
        router_state(BIAS))


@pytest.mark.parametrize("route", ["fallback", "kernel"])
def test_keeping_the_flash_residuals_changes_no_value_on_the_cpu(route, monkeypatch):
    """`forward` checkpoints every layer with `pallas_attention.
    KEEP_RESIDUALS`: where a recomputation repeats the forward pass to the
    bit, as here on the CPU, the loss terms and every gradient leaf are those
    of the plain `jax.checkpoint` it had before, exactly. On the kernel route
    (64 tokens, interpret mode) a layer's recomputation then holds no second
    forward call — four blocks, four calls for eight; on the XLA fallback (36
    tokens) none of the names occurs and the policy is inert."""
    from elasticdl_tpu.ops import pallas_attention

    spec, _ = lm.fresh_trainer()
    batch, params = lm.batches(steps=1, seq=64 if route == "kernel" else 36)[0], lm.params()
    if route == "kernel":
        # the signal without `force_tpu_interpret_mode`, whose callbacks a
        # remat refuses (tests/test_nemotron_h.py::interpret_kernels)
        monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
        monkeypatch.setenv("EDL_FLASH", "1")

    def value_and_grad():       # a new closure each time: a new trace
        def program_loss(p):
            terms = lm.terms(spec, p, batch, router_state(BIAS))
            return terms["loss"], terms
        return jax.value_and_grad(program_loss, has_aux=True)

    forward_calls = lambda: pallas_calls(
        jax.make_jaxpr(value_and_grad())(params).jaxpr, "flash_attention_fwd")
    got_calls, ((_, got_terms), got) = forward_calls(), jax.jit(value_and_grad())(params)
    monkeypatch.setattr(pallas_attention, "KEEP_RESIDUALS", None)   # the plain form
    want_calls, ((_, want_terms), want) = forward_calls(), jax.jit(value_and_grad())(params)
    assert (got_calls, want_calls) == ((4, 8) if route == "kernel" else (0, 0))
    for term in ("loss", "loss_main", "loss_mtp"):
        assert float(got_terms[term]) == float(want_terms[term]), term
    assert sorted(got) == sorted(LEAVES)
    for leaf in LEAVES:
        assert float(jnp.max(jnp.abs(want[leaf]))) > 0, leaf
        np.testing.assert_array_equal(np.asarray(got[leaf]), np.asarray(want[leaf]), err_msg=leaf)


@pytest.mark.parametrize("term", ["loss", "loss_main", "loss_mtp"])
def test_loss_terms_match_reference(gradients, term):
    ((total, got), _), ((ref_total, want), _) = gradients
    got, want = ({**got, "loss": total}[term], {**want, "loss": ref_total}[term])
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    assert float(want) > np.log(TINY["vocab_size"]) - 0.5          # untrained


def test_the_loss_is_main_plus_three_tenths_of_the_module_s(gradients):
    ((total, terms), _), _ = gradients
    np.testing.assert_allclose(
        float(total), float(terms["loss_main"]) + 0.3 * float(terms["loss_mtp"]), rtol=1e-6)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_reference(gradients, leaf):
    (_, got), (_, want) = gradients
    assert got[leaf].shape == want[leaf].shape
    assert np.linalg.norm(want[leaf]) > 0
    assert check_lm._rel_l2(np.asarray(got[leaf]), np.asarray(want[leaf])) < 1e-4


# ------------------------------------------------------------------ #
# latent attention, by hand


def by_hand_attention(p, x, cfg, rotary_key_columns=None):
    """A plain multi-head attention whose q, k and v are built from the
    low-rank factors, every head with a k of its own (nope | rope) wide;
    `rotary_key_columns` (heads, C, rope): the rotary key's down-projection
    given to each head apart, in place of the one all heads share."""
    m = zoo()
    b, t, c = x.shape
    heads, nope, rot, rank = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                              cfg.qk_rope_head_dim, cfg.kv_lora_rank)
    h = m.rmsnorm(x, p["attn_norm"], cfg.rms_norm_eps)
    c_q = m.rmsnorm(h @ p["q_a"], p["q_a_norm"], cfg.rms_norm_eps)
    q = (c_q @ p["q_b"]).reshape(b, t, heads, nope + rot)
    c_kv = m.rmsnorm(h @ p["kv_a"][:, :rank], p["kv_a_norm"], cfg.rms_norm_eps)
    kv = (c_kv @ p["kv_b"]).reshape(b, t, heads, nope + cfg.v_head_dim)
    if rotary_key_columns is None:
        rotary_key_columns = jnp.stack([p["kv_a"][:, rank:]] * heads)
    k_r = jnp.einsum("btc,hcr->bthr", h, rotary_key_columns)
    q = jnp.concatenate([q[..., :nope], m.rope(q[..., nope:], cfg.rope_theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], m.rope(k_r, cfg.rope_theta)], axis=-1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(nope + rot)
    scores = jnp.where(np.tril(np.ones((t, t), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), kv[..., nope:])
    return out.reshape(b, t, -1) @ p["wo"]


def one_attention_layer(seed=7):
    m = zoo()
    cfg = m.Config(**TINY)
    r = np.random.default_rng(seed)
    c, heads, qk = 48, 4, 16
    shapes = {"attn_norm": (c,), "q_a": (c, 24), "q_a_norm": (24,), "q_b": (24, heads * qk),
              "kv_a": (c, 16 + 8), "kv_a_norm": (16,), "kv_b": (16, heads * (8 + 16)),
              "wo": (heads * 16, c)}
    p = {k: jnp.asarray(r.uniform(0.5, 1.5, s) if k.endswith("norm")
                        else r.normal(size=s) * 0.4, jnp.float32)
         for k, s in shapes.items()}
    x = jnp.asarray(r.normal(size=(2, 11, c)), jnp.float32)
    return m, cfg, p, x


def test_latent_attention_is_plain_attention_over_the_low_rank_factors():
    m, cfg, p, x = one_attention_layer()
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(m.latent_attention(p, x, cfg),
                                   by_hand_attention(p, x, cfg), rtol=2e-4, atol=2e-5)
        hp = reference.hyper(lm.tiny_params())
        np.testing.assert_allclose(reference.attention(p, x, hp),
                                   by_hand_attention(p, x, cfg), rtol=2e-4, atol=2e-5)


def test_the_shared_rotary_key_takes_the_sum_of_the_heads_gradients():
    m, cfg, p, x = one_attention_layer()
    rank = cfg.kv_lora_rank
    probe = jnp.asarray(np.random.default_rng(8).normal(size=x.shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: jnp.sum(probe * m.latent_attention(p, x, cfg)))(p)
        per_head = jnp.stack([p["kv_a"][:, rank:]] * cfg.num_attention_heads)
        each = jax.grad(lambda cols: jnp.sum(
            probe * by_hand_attention(p, x, cfg, cols)))(per_head)
    assert np.linalg.norm(each[0] - each[1]) > 1e-3 * np.linalg.norm(each[0])
    np.testing.assert_allclose(got["kv_a"][:, rank:], jnp.sum(each, axis=0),
                               rtol=2e-3, atol=1e-5)


def test_queries_and_values_must_share_a_head_size():
    with pytest.raises(ValueError, match="one head size"):
        zoo().Config(qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=128)
    with pytest.raises(ValueError, match="0 or 1"):
        zoo().Config(num_nextn_predict_layers=2)


# ------------------------------------------------------------------ #
# the multi-token-prediction module, by hand


def test_the_module_s_shift_mask_and_shared_head_by_hand():
    """Position i of the module sees the embedding of token i + 1 and
    predicts token i + 2; T − 1 positions have a target; the head and the
    embedding each take both streams' gradients."""
    (spec, _), batch, params = lm.trainer(), lm.batches(steps=1)[0], lm.params()
    m = zoo()
    apply = jax.jit(lambda p, feats: spec.model.apply(      # traced once for every use below
        {"params": p, **router_state(BIAS)}, feats, training=False))
    outputs = apply(params, batch["features"])
    t = batch["features"].shape[1]
    assert outputs["logits"].shape == outputs["mtp_logits"].shape == (2, t, 256)
    terms = spec.loss(batch["labels"], outputs)
    logp = np.asarray(jax.nn.log_softmax(outputs["mtp_logits"], axis=-1))
    tokens = np.concatenate([batch["features"], batch["labels"][:, -1:]], axis=1)  # t_0..t_T
    by_hand = [-np.mean([logp[b, i, tokens[b, i + 2]] for i in range(t - 1)])
               for b in range(2)]
    np.testing.assert_allclose(terms["loss_mtp"], by_hand, rtol=1e-5)
    # the last position has no target: whatever its logits, the loss stays
    spoiled = {**outputs, "mtp_logits": outputs["mtp_logits"].at[:, -1].set(7.0)}
    np.testing.assert_allclose(spec.loss(batch["labels"], spoiled)["loss_mtp"],
                               terms["loss_mtp"], rtol=1e-6)
    # position i does see token i + 1: change token j and the module's logits
    # move at position j − 1, the main stream's only from position j on
    j = 20
    changed = np.array(batch["features"])
    changed[:, j] = (changed[:, j] + 1) % 256
    moved = apply(params, changed)
    differs = lambda name: np.abs(np.asarray(moved[name] - outputs[name])).max(axis=(0, 2)) > 1e-6
    assert not differs("logits")[:j].any() and differs("logits")[j]
    assert not differs("mtp_logits")[:j - 1].any() and differs("mtp_logits")[j - 1]
    # one head, one embedding: each term alone reaches both
    grads = {term: jax.grad(lambda p: jnp.mean(
        spec.loss(batch["labels"], apply(p, batch["features"]))[term]))(params)
        for term in ("loss_main", "loss_mtp")}
    for g in grads.values():
        assert np.linalg.norm(g["head"]) > 0 and np.linalg.norm(g["embed"]) > 0
    assert not np.any(np.asarray(grads["loss_main"]["mtp_eh_proj"]))
    assert m.MTP_LOSS_WEIGHT == reference.MTP_LOSS_WEIGHT == 0.3


def test_the_step_reports_both_terms_and_evaluation_reads_both_streams():
    spec, trainer = lm.trainer(warmup_steps=1)
    data = lm.batches(steps=1)[0]
    state = trainer.init_state(data)
    bias = lambda s: np.asarray(s.extra_vars["router_state"]["e_score_correction_bias"])
    assert bias(state).shape == (3, 16) and not bias(state).any()
    results = trainer.metric_results(
        trainer.eval_step(state, data, trainer.new_metric_states()))
    assert set(results) == {"token_accuracy", "mtp_token_accuracy", "loss"}
    assert not bias(state).any()                       # evaluation leaves the bias alone
    state, logs = trainer.train_step(state, data)
    assert set(logs) == {"loss", "loss_main", "loss_mtp"}
    np.testing.assert_allclose(
        float(logs["loss"]), float(logs["loss_main"]) + 0.3 * float(logs["loss_mtp"]),
        rtol=1e-6)
    assert np.allclose(np.abs(bias(state)), 1e-3)
    passes = state.extra_vars["router_state"]["held_passes"]
    np.testing.assert_array_equal(passes, [1, 1, 1])    # two sparse layers and the module's


def test_custom_model_ignores_the_harness_keys_and_trains():
    spec, trainer = lm.trainer(warmup_steps=1)
    model = zoo().custom_model(field_vocab="512", **lm.tiny_params())
    assert model.cfg == spec.model.cfg
    data = lm.batches(steps=1)[0]
    state = trainer.init_state(data)
    losses = []
    for _ in range(8):
        state, m = trainer.train_step(state, data)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3


@pytest.mark.parametrize("modules,card", [(0, 29_943_390_976), (1, 30_587_097_088)])
def test_published_defaults_count_the_card_s_parameters(modules, card):
    model = zoo().custom_model(num_nextn_predict_layers=modules)
    assert model.cfg.held == (0, 64) and model.cfg.sparse_layers == 46
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes["params"]))
    assert count == card
    published = {k: str(getattr(model.cfg, k)) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "num_experts_per_tok",
        "moe_intermediate_size", "num_nextn_predict_layers")}
    assert flops.parameter_count(published) == card


# ------------------------------------------------------------------ #
# the share of a deployment, tied to the whole (model-configs guide §4)


def test_four_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """One sparse feed-forward at 16 experts top-3: the routed parts that 4
    shares of 4 experts compute (the program's held dispatch, the shared
    expert taken away) plus the shared expert ONCE equal what the reference
    gives for the layer with every expert held — as the cell's eight shares
    of 8 make its 64."""
    m = zoo()
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
    hp_whole = reference.hyper(lm.tiny_params(n_routed_experts=16, first_expert=0))
    with jax.default_matmul_precision("highest"):
        want, _, _ = jax.jit(lambda p, x: reference.moe(p, x, bias, None, hp_whole))(whole, x)
        shared = m.gated_mlp(
            m.rmsnorm(x, whole["moe_norm"], 1e-5).reshape(-1, c), whole["shared_gate"],
            whole["shared_up"], whole["shared_down"], jnp.float32).reshape(x.shape)
        total = shared
        for share in range(4):
            cfg = m.Config(**{**TINY, "first_expert": 4 * share})
            held = slice(4 * share, 4 * share + 4)
            part = {**whole, "w_gate": whole["w_gate"][held], "w_up": whole["w_up"][held],
                    "w_down": whole["w_down"][held]}
            y, _ = jax.jit(lambda p, x: m.moe(p, x, bias, cfg))(part, x)
            total = total + (y - shared)
            # and the reference, given the same share, gives the same part
            hp = reference.hyper(lm.tiny_params(first_expert=4 * share))
            ref_part, _, _ = jax.jit(lambda p, x: reference.moe(p, x, bias, None, hp))(part, x)
            np.testing.assert_allclose(y, ref_part, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
