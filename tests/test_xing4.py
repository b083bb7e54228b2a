"""Xing4.0 (model_zoo/transformer/xing4.py: a residual state of `hc_mult`
streams mixed at every sub-block by learned maps, the stream-to-stream one
doubly stochastic by 20 Sinkhorn rounds; latent attention with q and k heads
wider than its v heads under YaRN; a dense layer, held gated-SiLU experts
behind a sigmoid router with a selection bias, a shared expert) against its
plain reference (benchmark/reference/xing4.py) on seeded weights, at a tiny
size on the CPU: hidden 48, one dense and two sparse layers, 4 heads of 16 + 8
beside 8, an original context of 16 under 36 tokens, 16 experts top-3 of which
experts 4-7 are held, vocabulary 256, float32.

The benchmark's own comparison, and the departures it must catch, are in
`tests/test_xing4_check.py`.
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

TINY = zoo_lm.preset("tiny-lm-xing.json")
NORMS = ("final_norm", "attn_norm", "q_a_norm", "kv_a_norm", "mlp_norm", "moe_norm")
MATRICES = ("q_a", "q_b", "kv_a", "kv_b", "wo", "mlp_gate", "mlp_up", "mlp_down",
            "shared_gate", "shared_up", "shared_down", "w_gate", "w_up", "w_down")
HC = ("hc_phi", "hc_alpha", "hc_b")
LEAVES = ("embed", "head", "moe_router") + NORMS + MATRICES + HC

reference = common.load_module("reference", "xing4")
flops = common.load_module("flops", "xing4")
driver = common.load_module("drivers", "resident_lm_model")
departures = common.load_module("rehearse", "departures_xing4")

# router logits of order one, every norm's weight away from one, projections
# large enough that attention is far from a running mean; the three gates
# thirty times their seed and the biases jittered, so that the maps differ
# from token to token and from stream to stream
LIVELY = [(("moe_router",), zoo_lm.scaled(8.0)),
          (NORMS, zoo_lm.jittered),
          (MATRICES, zoo_lm.scaled(6.0)),
          (("hc_alpha",), zoo_lm.scaled(30.0)),
          (("hc_b",), lambda leaf, r: leaf + jnp.asarray(
              r.normal(size=leaf.shape) * 0.5, jnp.float32))]


def harness(**more):
    return zoo_lm.ZooLM(
        "xing4", tiny={**TINY, **more}, reference=reference, driver=driver,
        departures=departures, seq=36, mutable=("router_state", "attn"), training=True,
        lively=LIVELY,
        # the check's cases run the dense layer and one sparse layer
        short={"num_hidden_layers": 2})


lm = harness()
STREAMS = {4: lm, 2: harness(hc_mult=2)}
# a selection bias that is not zero
BIAS = jnp.asarray(np.random.default_rng(2).normal(size=(2, 16)) * 0.02, jnp.float32)


def zoo():
    return lm.zoo


def cfg_of(**more):
    return zoo().custom_model(**lm.tiny_params(**more)).cfg


def router_state(bias):
    zeros = jnp.zeros((bias.shape[0],), jnp.int32)
    return {"router_state": {"e_score_correction_bias": bias, "held_passes": zeros,
                             "held_row_tiles": zeros, "held_row_chunks": zeros},
            "attn": {"kv_block_visits": jnp.zeros((), jnp.int32)}}


@pytest.fixture(scope="module", params=sorted(STREAMS))
def gradients(request):
    """(program's, reference's) loss terms and gradients of one batch from
    the same lively parameters and a selection bias that is not zero, at four
    streams and at two."""
    return STREAMS[request.param].gradients(
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


def test_the_residual_the_step_reports_is_the_reference_s(gradients):
    """`mhc_sinkhorn_residual` rides along with the loss's terms: a row sum
    less one, so float32's last bits are a thousandth of it."""
    ((_, got), _), ((_, want), _) = gradients
    assert set(got) - {"loss"} == set(want) == {"loss_ce", "mhc_sinkhorn_residual"}
    assert 1e-6 < float(want["mhc_sinkhorn_residual"]) < 1e-1      # lively parameters
    assert float(got["mhc_sinkhorn_residual"]) == pytest.approx(
        float(want["mhc_sinkhorn_residual"]), rel=2e-2)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_reference(gradients, leaf):
    (_, got), (_, want) = gradients
    assert got[leaf].shape == want[leaf].shape
    assert np.linalg.norm(want[leaf]) > 0
    assert check_lm._rel_l2(np.asarray(got[leaf]), np.asarray(want[leaf])) < 1e-4


def test_every_group_of_coefficients_has_a_gradient(gradients):
    """H_pre, H_post and H_res each move the loss: the three gates apart."""
    (_, got), _ = gradients
    assert np.all(np.abs(np.asarray(got["hc_alpha"])) > 0)


# ------------------------------------------------------------------ #
# the streams, by hand


def positive_matrices(seed=4, tokens=7, n=4):
    r = np.random.default_rng(seed)
    near_identity = 4.0 * np.eye(n)[:, :, None, None] + r.normal(size=(n, n, 2, tokens))
    return jnp.asarray(np.exp(near_identity), jnp.float32)


def test_h_res_is_doubly_stochastic_to_the_residual_the_model_reports():
    m = zoo()
    h_res = m.sinkhorn(positive_matrices(), 20, 1e-6)
    assert float(jnp.min(h_res)) > 0
    # the columns were normalised last: exact; the rows to what 20 rounds leave
    np.testing.assert_allclose(jnp.sum(h_res, axis=0), 1.0, atol=3e-6)
    rows = float(jnp.max(jnp.abs(jnp.sum(h_res, axis=1) - 1.0)))
    residual, off_diagonal = m.mhc_stats(h_res)
    assert residual.shape == off_diagonal.shape == (2,)
    np.testing.assert_allclose(float(jnp.max(residual)), rows, rtol=1e-5)
    assert 1e-7 < rows < 5e-2          # near the identity the rounds converge slowly
    fewer = m.sinkhorn(positive_matrices(), 10, 1e-6)
    assert float(jnp.max(jnp.abs(jnp.sum(fewer, axis=1) - 1.0))) > 2 * rows
    assert 0.0 < float(jnp.max(off_diagonal)) < 0.5


def test_sinkhorn_is_the_reference_s_unrolled_loop_value_and_gradient():
    """The scan against twenty rounds written out (the reference keeps the
    matrix in the LAST two axes), and its gradient against `jax.grad` through
    the written-out loop."""
    m = zoo()
    matrices = positive_matrices(seed=5)
    last = lambda x: jnp.moveaxis(x, (0, 1), (-2, -1))
    np.testing.assert_allclose(last(m.sinkhorn(matrices, 20, 1e-6)),
                               reference.sinkhorn(last(matrices), 20, 1e-6), rtol=2e-6)
    probe = jnp.asarray(np.random.default_rng(6).normal(size=matrices.shape), jnp.float32)
    got = jax.grad(lambda x: jnp.sum(probe * m.sinkhorn(x, 20, 1e-6)))(matrices)
    want = jax.grad(lambda x: jnp.sum(last(probe) * reference.sinkhorn(last(x), 20, 1e-6)))(
        matrices)
    assert float(jnp.max(jnp.abs(want))) > 1e-4
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)


def test_the_scan_and_the_unrolled_rounds_are_the_same_values():
    """The program's rounds are ONE loop in its text; twenty rounds written
    out here give the same values."""
    m = zoo()
    matrices = positive_matrices(seed=7)
    scanned = jax.jit(lambda x: m.sinkhorn(x, 20, 1e-6))
    assert "while" in scanned.lower(matrices).as_text()

    def unrolled(x):
        for _ in range(20):
            x = x / (jnp.sum(x, axis=1, keepdims=True) + 1e-6)
            x = x / (jnp.sum(x, axis=0, keepdims=True) + 1e-6)
        return x

    assert "while" not in jax.jit(unrolled).lower(matrices).as_text()
    np.testing.assert_allclose(scanned(matrices), jax.jit(unrolled)(matrices), rtol=5e-6)


@pytest.mark.parametrize("which", ["pre", "post", "res"])
def test_coefficients_match_the_reference_s_on_a_random_state(which):
    """`mhc_coefficients` on a stream-major state against the reference's
    `connections` on (B, T, n, C): phi's rows are stream by stream either
    way, H_res row i the stream written."""
    m, cfg = zoo(), cfg_of()
    n, c, k = cfg.hc_mult, cfg.hidden_size, cfg.hc_coefficients
    r = np.random.default_rng(8)
    p = {"hc_phi": jnp.asarray(r.normal(size=(n * c, k)) * 0.3, jnp.float32),
         "hc_alpha": jnp.asarray([0.7, -0.4, 0.9], jnp.float32),
         "hc_b": jnp.asarray(r.normal(size=(k,)), jnp.float32)}
    state = jnp.asarray(r.normal(size=(2, 5, n, c)) * 3.0, jnp.float32)
    hp = reference.hyper(lm.tiny_params())
    with jax.default_matmul_precision("highest"):
        got = dict(zip(("pre", "post", "res"),
                       m.mhc_coefficients(p, jnp.moveaxis(state, 2, 0), cfg)))
        want = dict(zip(("pre", "post", "res"), reference.connections(p, state, hp)))
    got = {"pre": jnp.moveaxis(got["pre"], 0, -1), "post": jnp.moveaxis(got["post"], 0, -1),
           "res": jnp.moveaxis(got["res"], (0, 1), (-2, -1))}
    assert got[which].shape == want[which].shape
    np.testing.assert_allclose(got[which], want[which], rtol=2e-5, atol=1e-6)
    if which == "post":
        assert float(jnp.max(want["post"])) > 1.0          # 2 sigmoid: up to two


def test_a_sub_block_reads_and_writes_as_the_equations_say():
    m, cfg = zoo(), cfg_of()
    r = np.random.default_rng(9)
    n, b, t, c = 4, 2, 5, cfg.hidden_size
    streams = jnp.asarray(r.normal(size=(n, b, t, c)), jnp.float32)
    h_pre, h_post = (jnp.asarray(r.uniform(size=(n, b, t)), jnp.float32) for _ in range(2))
    h_res = jnp.asarray(r.uniform(size=(n, n, b, t)), jnp.float32)
    y = jnp.asarray(r.normal(size=(b, t, c)), jnp.float32)
    np.testing.assert_allclose(m.mhc_read(streams, h_pre),
                               jnp.einsum("nbt,nbtc->btc", h_pre, streams), rtol=1e-5, atol=1e-6)
    want = jnp.einsum("ijbt,jbtc->ibtc", h_res, streams) + h_post[..., None] * y[None]
    np.testing.assert_allclose(m.mhc_write(streams, y, h_post, h_res), want,
                               rtol=1e-5, atol=1e-6)
    # the write-back rounds ONCE, to the streams' dtype
    rounded = m.mhc_write(streams.astype(jnp.bfloat16), y, h_post, h_res)
    assert rounded.dtype == jnp.bfloat16
    exact = (jnp.einsum("ijbt,jbtc->ibtc", h_res, streams.astype(jnp.bfloat16).astype(jnp.float32))
             + h_post[..., None] * y[None])
    np.testing.assert_array_equal(rounded, exact.astype(jnp.bfloat16))


def one_stream_logits(params, tokens, cfg):
    """The same parameters as a ONE-stream model: `x + f(norm(x))` around the
    same sub-blocks (GLM's `layer`, given this model's rotary map and softmax
    factor through its attention)."""
    m = zoo()
    glm = m.glm
    table = m.yarn_table(cfg, tokens.shape[1])
    attention = lambda p, x: glm.latent_attention(
        p, x, cfg, rotate=lambda part: m.rotate(part, table), q_scale=cfg.softmax_factor)
    x = jnp.take(params["embed"], tokens, axis=0)
    for i in range(cfg.num_hidden_layers):
        dense = i < cfg.first_k_dense_replace
        p = m._layer_params(params, i, glm.DENSE_KEYS if dense else glm.SPARSE_KEYS,
                            i if dense else i - cfg.first_k_dense_replace)
        x = x + attention(p, x)
        x = x + (glm.dense_mlp(p, x, cfg) if dense else glm.moe(
            p, x, jnp.zeros((cfg.num_experts,), jnp.float32), cfg)[0])
    return glm._head(x, params["final_norm"], params["head"], cfg)


def test_one_stream_with_its_maps_fixed_at_one_is_a_one_stream_model(monkeypatch):
    one = harness(hc_mult=1)
    m, cfg = one.zoo, one.trainer()[0].model.cfg
    params, tokens = one.params(), one.batches(steps=1)[0]["features"]
    ones = lambda p, streams, cfg: (jnp.ones(streams.shape[:3]), jnp.ones(streams.shape[:3]),
                                    jnp.ones((1,) + streams.shape[:3]))
    monkeypatch.setattr(m, "mhc_coefficients", ones)
    bias = jnp.zeros((cfg.sparse_layers, cfg.num_experts), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = m.forward(params, bias, tokens, cfg)[0]["logits"]
        want = one_stream_logits(params, tokens, cfg)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_at_the_seed_the_four_streams_are_near_a_one_stream_model():
    """The initialisation the configuration assumes: H_pre = 1/4 each over
    four equal streams, H_post = 1, H_res near the identity — with the gates
    at zero exactly a one-stream model (to the Sinkhorn residual), at 0.01
    near it."""
    spec, trainer = lm.trainer()
    m, cfg = zoo(), spec.model.cfg
    data = lm.batches(steps=1)[0]
    params = trainer.init_state(data).params
    assert float(jnp.max(jnp.abs(params["hc_alpha"] - 0.01))) == 0.0
    b = np.asarray(params["hc_b"][0])
    np.testing.assert_allclose(b[:4], -math.log(3.0), rtol=1e-6)
    assert not b[4:8].any()
    np.testing.assert_array_equal(b[8:].reshape(4, 4), 4.0 * np.eye(4))
    bias = jnp.zeros((cfg.sparse_layers, cfg.num_experts), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = one_stream_logits(params, data["features"], cfg)
        outputs = m.forward(params, bias, data["features"], cfg)[0]
        gates_shut = m.forward({**params, "hc_alpha": jnp.zeros_like(params["hc_alpha"])},
                               bias, data["features"], cfg)[0]["logits"]
    np.testing.assert_allclose(gates_shut, want, atol=3e-3)
    assert float(jnp.max(jnp.abs(outputs["logits"] - want))) < 5e-2
    residual, off_diagonal = np.asarray(outputs["mhc_stats"]).T
    assert np.all(residual < 1e-3) and np.all(np.abs(off_diagonal - 0.052) < 2e-3)


# ------------------------------------------------------------------ #
# latent attention at two widths under YaRN


def test_yarn_s_table_and_softmax_factor_in_closed_form():
    m = zoo()
    cfg = m.Config()                   # the published keys
    assert (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim) == (192, 128)
    assert cfg.softmax_factor == pytest.approx((0.1 * math.log(64.0) + 1.0) ** 2)
    assert cfg.softmax_factor == pytest.approx(1.4159 ** 2, rel=1e-4)
    cos, sin = m.yarn_table(cfg, 4096)
    assert cos.shape == sin.shape == (1, 4096, 1, 64)
    # dimension i turns 4096 theta^(-2i/64) / 2 pi times over the original
    # context: more than 32 up to i = 10 (kept), fewer than 1 from i = 23 on
    # (divided by 64), a linear ramp over the 13 between
    turns = lambda i: 4096 * 1e4 ** (-2 * i / 64) / (2 * math.pi)
    assert turns(10) > 32 > turns(11) and turns(22) > 1 > turns(23)
    plain = np.array([1e4 ** (-2 * i / 64) for i in range(32)])
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    inv_freq = (1 - ramp) * plain + ramp * plain / 64
    np.testing.assert_allclose(reference.yarn_frequencies(reference.hyper(
        {**lm.tiny_params(), "qk_rope_head_dim": "64", "original_max_position_embeddings": "4096"})),
        inv_freq, rtol=1e-6)
    for position in (1, 5, 100):
        np.testing.assert_allclose(np.asarray(cos)[0, position, 0, :32],
                                   np.cos(position * inv_freq), atol=2e-5)
        np.testing.assert_allclose(np.asarray(sin)[0, position, 0, 32:],
                                   np.sin(position * inv_freq), atol=2e-5)
    assert reference.softmax_factor({"rope_factor": 64.0, "mscale_all_dim": 1.0}) \
        == pytest.approx(cfg.softmax_factor)


def test_latent_attention_at_two_widths_matches_the_reference_s():
    m, cfg = zoo(), cfg_of()
    r = np.random.default_rng(10)
    c, heads, qk, v = 48, 4, 24, 8
    shapes = {"attn_norm": (c,), "q_a": (c, 24), "q_a_norm": (24,), "q_b": (24, heads * qk),
              "kv_a": (c, 16 + 8), "kv_a_norm": (16,), "kv_b": (16, heads * (16 + v)),
              "wo": (heads * v, c)}
    p = {k: jnp.asarray(r.uniform(0.5, 1.5, s) if k.endswith("norm")
                        else r.normal(size=s) * 0.4, jnp.float32) for k, s in shapes.items()}
    x = jnp.asarray(r.normal(size=(2, 21, c)), jnp.float32)
    table = m.yarn_table(cfg, 21)
    hp = reference.hyper(lm.tiny_params())
    with jax.default_matmul_precision("highest"):
        got = m.glm.latent_attention(p, x, cfg, rotate=lambda part: m.rotate(part, table),
                                     q_scale=cfg.softmax_factor)
        np.testing.assert_allclose(got, reference.attention(p, x, hp), rtol=2e-4, atol=2e-5)
        unscaled = m.glm.latent_attention(p, x, cfg, rotate=lambda part: m.rotate(part, table))
    assert float(jnp.max(jnp.abs(got - unscaled))) > 1e-3


def test_the_model_runs_the_flash_kernels_at_two_widths(monkeypatch):
    """q and k 24 wide, v 8: one forward kernel a layer in the step, and the
    loss the XLA path's."""
    data = lm.batches(steps=1, seq=32)[0]
    losses = {}
    for flash in ("0", "1"):
        monkeypatch.setenv("EDL_FLASH", flash)
        monkeypatch.setenv(pallas_attention._INTERPRET_ENV, flash)
        spec, _ = lm.fresh_trainer()
        f = lambda p, spec=spec: lm.terms(spec, p, data, router_state(BIAS))["loss"]
        if flash == "1":
            jaxpr = jax.make_jaxpr(jax.grad(f))(lm.params()).jaxpr
            assert pallas_calls(jaxpr, "flash_attention_fwd") == 3
            assert pallas_calls(jaxpr, "flash_attention_bwd") == 3
        losses[flash] = float(f(lm.params()))
    assert losses["1"] == pytest.approx(losses["0"], rel=1e-5)


# ------------------------------------------------------------------ #
# the zoo contract


def test_the_multi_token_prediction_module_is_refused_not_guessed():
    with pytest.raises(ValueError, match="multi-token-prediction module is not built"):
        zoo().custom_model(**lm.tiny_params(num_nextn_predict_layers=1))
    with pytest.raises(ValueError, match="has no reference"):
        reference.hyper(lm.tiny_params(num_nextn_predict_layers=1))
    with pytest.raises(ValueError, match="mscale"):
        zoo().Config(mscale=0.707)


def test_the_step_reports_its_term_the_counters_and_the_streams_figures():
    spec, trainer = lm.trainer(warmup_steps=1)
    data = lm.batches(steps=1)[0]
    state = trainer.init_state(data)
    bias = lambda s: np.asarray(s.extra_vars["router_state"]["e_score_correction_bias"])
    assert bias(state).shape == (2, 16) and not bias(state).any()
    results = trainer.metric_results(
        trainer.eval_step(state, data, trainer.new_metric_states()))
    assert set(results) == {"token_accuracy", "mhc_sinkhorn_residual", "mhc_h_res_offdiag", "loss"}
    assert 0 < results["mhc_sinkhorn_residual"] < 1e-3
    assert results["mhc_h_res_offdiag"] == pytest.approx(0.052, abs=2e-3)
    assert not bias(state).any()                       # evaluation leaves the bias alone
    state, logs = trainer.train_step(state, data)
    assert set(logs) == {"loss", "loss_ce", "mhc_sinkhorn_residual"}
    assert 0 < float(logs["mhc_sinkhorn_residual"]) < 1e-3
    assert np.allclose(np.abs(bias(state)), 1e-3)
    np.testing.assert_array_equal(state.extra_vars["router_state"]["held_passes"], [1, 1])
    # off the chip no shape is blocked for the kernels: three layers of (0, 0)
    assert int(state.extra_vars["attn"]["kv_block_visits"]) == 3 * \
        pallas_attention.kv_block_visits(36, 36, None, 24, jnp.float32)[1]
    assert zoo().kv_block_visits(zoo().Config(num_hidden_layers=5), 4096) == 5 * 10


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


def test_bfloat16_streams_are_stored_so_and_move_the_loss_little():
    """The streams take the compute dtype: bfloat16 wherever the matmuls'
    operands are (the chip), float32 in this file's other cases."""
    data = lm.batches(steps=1)[0]
    assert "stream_dtype" not in zoo().Config.__dataclass_fields__
    spec, _ = lm.fresh_trainer(compute_dtype="bfloat16")
    narrow = float(lm.terms(spec, lm.params(), data, router_state(BIAS))["loss"])
    wide = float(lm.program_terms()(lm.params(), data, router_state(BIAS))["loss"])
    assert narrow != wide and narrow == pytest.approx(wide, rel=1e-2)
    jaxpr = jax.make_jaxpr(lambda p: lm.terms(spec, p, data, router_state(BIAS))["loss"])(
        lm.params())
    assert "bf16[4,2,36,48]" in str(jaxpr)             # the state, stream-major


def test_published_defaults_count_the_uncut_model_s_parameters():
    model = zoo().custom_model()
    assert model.cfg.held == (0, 64) and model.cfg.sparse_layers == 38
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes["params"]))
    published = {k: str(getattr(model.cfg, k)) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "first_k_dense_replace",
        "intermediate_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
        "num_experts_per_tok", "moe_intermediate_size")}
    assert flops.parameter_count(published) == count == 29_505_502_832
    assert flops.active_parameter_count(published) == 3_932_487_680     # the card's A4B


# ------------------------------------------------------------------ #
# the share of a deployment, tied to the whole (model-configs guide §4)


def test_eight_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """One sparse feed-forward at 16 experts top-3: the routed parts that 8
    shares of 2 experts compute (the program's held dispatch, the shared
    expert taken away) plus the shared expert ONCE equal what the reference
    gives for the layer with every expert held — as the cell's eight shares
    of 8 make its 64."""
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
    hp_whole = reference.hyper(lm.tiny_params(n_routed_experts=16, first_expert=0))
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p, x: reference.moe(p, x, bias, None, hp_whole))(whole, x)
        shared = m.gated_mlp(
            m.rmsnorm(x, whole["moe_norm"], 1e-6).reshape(-1, c), whole["shared_gate"],
            whole["shared_up"], whole["shared_down"], jnp.float32).reshape(x.shape)
        total = shared
        for share in range(8):
            cfg = cfg_of(n_routed_experts=2, first_expert=2 * share)
            held = slice(2 * share, 2 * share + 2)
            p = {**whole, **{k: whole[k][held] for k in ("w_gate", "w_up", "w_down")}}
            y, stats = m.moe(p, x, bias, cfg)
            total = total + (y - shared)
            assert stats["expert_idx"].shape == (18, 3)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)
    assert float(jnp.max(jnp.abs(want - shared))) > 1e-2
