"""Kimi Linear through the zoo contract, at the tiny preset of
`tests/test_kimi_linear.py` on the CPU: the recomputation policy changes no
value, the step's metrics and the counters it threads through
`TrainState.extra_vars`, `custom_model`'s keys, the initialisation the
configuration assumes, and the published defaults' parameter count. A file of
its own so that two xdist workers share the model's cases.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from tests.test_kimi_linear import (
    BIAS, RESIDUAL_WRITES, SEQ, collections, flops, lm, zoo)



def test_values_are_the_same_with_and_without_the_recomputation_policy(monkeypatch):
    """What a layer keeps by name (`KEEP`) changes what is recomputed, not a
    value: loss and gradients to float32's last bits (the recomputed sweep
    is fused differently)."""
    data = lm.batches(steps=1)[0]
    results = {}
    for keep in ("named", "nothing"):
        if keep == "nothing":
            monkeypatch.setattr(zoo(), "KEEP", None)
        spec, _ = lm.fresh_trainer(**lm.short)          # two layers, one of each kind
        f = lambda p, spec=spec: lm.terms(spec, p, data, collections(BIAS[:1], 1))["loss"]
        results[keep] = jax.jit(jax.value_and_grad(f))(lm.params(**lm.short))
    np.testing.assert_allclose(results["named"][0], results["nothing"][0], rtol=1e-6)
    for leaf in ("kda_wq", "kda_f_a", "kda_A_log", "q_proj", "w_gate", "embed"):
        want = np.asarray(results["nothing"][1][leaf])
        np.testing.assert_allclose(results["named"][1][leaf], want, rtol=1e-4,
                                   atol=1e-5 * float(np.max(np.abs(want))))


def test_the_step_reports_its_term_and_the_counters():
    spec, trainer = lm.trainer(warmup_steps=1, **lm.short)      # KDA, latent
    data = lm.batches(steps=1)[0]
    state = trainer.init_state(data)
    bias = lambda s: np.asarray(s.extra_vars["router_state"]["e_score_correction_bias"])
    assert bias(state).shape == (1, 16) and not bias(state).any()
    results = trainer.metric_results(
        trainer.eval_step(state, data, trainer.new_metric_states()))
    assert set(results) == {"token_accuracy", "kda_log_decay_min", "kda_beta_mean",
                            "kda_state_rms", "loss"}
    assert results["kda_log_decay_min"] < 0 < results["kda_state_rms"]
    assert results["kda_beta_mean"] == pytest.approx(0.5, abs=0.05)
    assert not bias(state).any()                       # evaluation leaves the bias alone
    state, logs = trainer.train_step(state, data)
    assert set(logs) == {"loss", "loss_ce"}
    # ±1e-3, and 0 where an expert's load is the mean to the pair (15 of 240)
    moved = np.abs(bias(state))
    assert np.all(np.isclose(moved, 1e-3) | (moved == 0)) and np.mean(moved > 0) > 0.8
    np.testing.assert_array_equal(state.extra_vars["router_state"]["held_passes"], [1])
    kda = state.extra_vars["kda"]
    # 1 KDA layer x 2 sequences x 4 heads x ceil(40 / 16) chunks
    assert int(kda["chunks"]) == 2 * 4 * 3 == zoo().chunks_walked(spec.model.cfg, 2, SEQ)
    assert kda["log_decay_min"].shape == (1,) and np.all(np.asarray(kda["log_decay_min"]) < 0)
    assert np.all(np.abs(np.asarray(kda["beta_mean"]) - 0.5) < 0.05)
    assert np.all(np.asarray(kda["state_rms"]) > 0)
    state, _ = trainer.train_step(state, data)
    assert int(state.extra_vars["kda"]["chunks"]) == 2 * 2 * 4 * 3


def test_custom_model_ignores_the_harness_keys_and_trains():
    spec, trainer = lm.trainer(warmup_steps=1, **lm.short)
    model = zoo().custom_model(field_vocab="512", **lm.tiny_params(warmup_steps=1, **lm.short))
    assert model.cfg == spec.model.cfg
    data = lm.batches(steps=1)[0]
    state = trainer.init_state(data)
    losses = []
    for _ in range(8):
        state, m = trainer.train_step(state, data)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.1


def test_the_initialisation_is_the_configuration_s():
    _, trainer = lm.trainer()
    params = trainer.init_state(lm.batches(steps=1)[0]).params
    std = lambda leaf: float(jnp.std(params[leaf]))
    assert std("embed") == pytest.approx(1.0, rel=0.05)
    assert std("kda_wq") == pytest.approx(0.02, rel=0.1)
    for leaf in RESIDUAL_WRITES:
        assert std(leaf) == pytest.approx(0.02 / (2 * 27) ** 0.5, rel=0.15), leaf
    a = np.exp(np.asarray(params["kda_A_log"]))
    assert np.all((1.0 <= a) & (a <= 16.0))
    step = np.log1p(np.exp(np.asarray(params["kda_dt_bias"])))      # softplus of the bias
    assert np.all((1e-3 * 0.99 <= step) & (step <= 0.1 * 1.01))
    assert float(jnp.max(jnp.abs(params["kda_onorm"] - 1.0))) == 0.0


def test_published_defaults_count_the_uncut_model_s_parameters():
    model = zoo().custom_model()
    assert model.cfg.held == (0, 256) and model.cfg.sparse_layers == 26
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes["params"]))
    cfg = model.cfg
    published = {k: str(getattr(cfg, k)) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "first_k_dense_replace",
        "intermediate_size", "linear_num_heads", "linear_head_dim", "num_attention_heads",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "num_experts_per_token", "moe_intermediate_size")}
    published["num_experts"] = str(cfg.held_experts)
    assert flops.parameter_count(published) == count == 49_122_675_072
    assert flops.active_parameter_count(published) == 3_484_453_248     # the card's A3B
    cell = common.model_params(common.load_json("configs", "kimi-linear-48b-a3b.json"))
    assert flops.parameter_count(cell) == 602_433_408
    assert flops.parameter_count(cell, published=True) == 49_122_675_072
    assert flops.active_parameter_count(cell, published=True) == 3_484_453_248
