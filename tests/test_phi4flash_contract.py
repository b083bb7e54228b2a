"""Phi-4-mini-flash through the zoo contract, at the tiny preset of
`tests/test_phi4flash.py` on the CPU: ALL 32 published layers against the
reference at narrower widths (the memory's cotangent from 7 readers, the shared
keys' and values' from 8), the step's metrics and the counters it threads
through `TrainState.extra_vars`, the scopes in the compiled text, the kernel
route of the scan inside a checkpointed layer, `custom_model`'s keys, the
initialisation the configuration assumes, the published defaults' parameter
count, and the departures the cell's check must catch. A file of its own so
that two xdist workers share the model's cases.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from elasticdl_tpu.ops import pallas_attention
from tests.conftest import pallas_calls
from tests.test_phi4flash import (
    ALL_LAYERS, LEAVES, assert_leaf_matches, departures, flops, lm, reference,
    reference_loss, zoo)

SEQ = 40


# ------------------------------------------------------------------ #
# all 32 published layers: 9 Mamba, 8 sliding, 1 full, 7 GMU, 7 cross


@pytest.fixture(scope="module")
def gradients_of_all_layers():
    """The same comparison over the whole published arrangement: the memory's
    cotangent is the sum over 7 GMUs, the shared keys' and values' over layer
    17 itself and 7 cross layers."""
    spec, trainer = lm.trainer(**ALL_LAYERS)
    batch = {k: v[:1, :24] if v.ndim == 2 else v[:1]
             for k, v in lm.batches(steps=1)[0].items()}
    batch["features"], batch["labels"] = batch["features"] % 64, batch["labels"] % 64
    params = lm.lively(trainer.init_state(batch)).params
    hp = reference.hyper(lm.tiny_params(**ALL_LAYERS))
    ref_batch = {"tokens": batch["features"], "labels": batch["labels"], "mask": batch["mask"]}
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(
            lambda p: lm.terms(spec, p, batch)["loss"]))(params)
        want = jax.jit(jax.value_and_grad(
            lambda p: reference_loss(p, ref_batch, hp)[0]))(params)
    return spec.model.cfg, got, want


def test_the_published_arrangement_has_its_kinds_in_their_numbers(gradients_of_all_layers):
    cfg, got, want = gradients_of_all_layers
    assert [cfg.layers_of(kind) for kind in zoo().KINDS] == [9, 8, 1, 7, 7]
    assert flops.layers_by_kind({**lm.tiny_params(**ALL_LAYERS)}) == dict(
        mamba=9, sliding=8, full=1, gmu=7, cross=7)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=5e-6)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradients_of_all_32_layers_match_reference(gradients_of_all_layers, leaf):
    _, (_, got), (_, want) = gradients_of_all_layers
    assert_leaf_matches(got, want, leaf, limit=5e-5)



# ------------------------------------------------------------------ #
# the zoo contract


def test_the_step_reports_its_loss_and_the_counters():
    spec, trainer = lm.trainer(warmup_steps=1)
    data = lm.batches(steps=1)[0]
    state = trainer.init_state(data)
    results = trainer.metric_results(
        trainer.eval_step(state, data, trainer.new_metric_states()))
    assert set(results) == {"token_accuracy", "loss"}
    assert not any(np.asarray(v).any() for group in ("s6", "memory", "shared_kv", "attn")
                   for v in state.extra_vars[group].values())      # evaluation counts nothing
    lambdas = np.asarray(state.params["attn_lambda"])       # the step donates the state
    state, logs = trainer.train_step(state, data)
    assert set(logs) == {"loss"}
    cfg, counted = spec.model.cfg, state.extra_vars
    # 2 Mamba layers x 2 sequences x 40 tokens x 128 channels x 16 state indices
    assert float(counted["s6"]["scan_elements"]) == 2 * 2 * SEQ * 128 * 16
    assert int(counted["memory"]["reads"]) == 1            # one GMU
    assert int(counted["shared_kv"]["reads"]) == 2         # layer 17 itself and one cross layer
    # one value an attention layer, by PUBLISHED index 1, 17, 19, from the
    # parameters the step read
    np.testing.assert_allclose(
        counted["diff_attn"]["lambda"],
        [zoo().attention_lambda(lambdas[at], i) for at, i in enumerate((1, 17, 19))], rtol=1e-5)
    assert 0.2 < float(counted["diff_attn"]["lambda"][0]) < zoo().lambda_init(17)
    visits, causal = zoo().kv_block_visits(cfg, SEQ)
    assert int(counted["attn"]["kv_block_visits"]) == visits
    assert int(counted["attn"]["kv_block_visits_causal"]) == causal >= visits
    state, _ = trainer.train_step(state, data)
    assert float(state.extra_vars["s6"]["scan_elements"]) == 2 * 2 * 2 * SEQ * 128 * 16
    assert int(state.extra_vars["memory"]["reads"]) == 2


SCOPES = ["embed", "mamba/proj", "mamba/conv", "mamba/dt", "mamba/scan", "mamba/gate_out",
          "gmu", "diff_attn/proj", "diff_attn/flash", "diff_attn/combine", "mlp", "norm",
          "head_loss"]


@pytest.fixture(scope="module")
def compiled_text():
    spec, _ = lm.trainer()
    data = lm.batches(steps=1)[0]
    f = lambda p: lm.terms(spec, p, data)["loss"]
    return jax.jit(jax.grad(f)).lower(lm.params()).compile().as_text()


@pytest.mark.parametrize("scope", SCOPES)
def test_every_scope_is_in_the_compiled_program_s_text(compiled_text, scope):
    assert f"phi4flash/{scope}" in compiled_text.replace("checkpoint/", "").replace(
        "rematted_computation/", "")
    assert f"phi4flash/{scope}" in [s for s in flops.SCOPES if s.endswith(scope)]


def test_a_checkpointed_mamba_layer_takes_the_scan_s_kernels(monkeypatch):
    """Under the interpret signal at whole time blocks (128 tokens, 128
    channels): a recomputed Mamba layer holds the forward kernel twice and the
    backward once, and its values are the plain route's."""
    spec, _ = lm.trainer()
    cfg = spec.model.cfg
    i, p = zoo().layer_parameters(lm.params(), cfg)[0]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 128, cfg.hidden_size))
    results = {}
    for route in ("plain", "kernel"):
        if route == "kernel":
            monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
        f = lambda p, x: jnp.sum(jax.checkpoint(
            lambda p, x: zoo().layer(p, x, cfg, i)[0])(p, x) ** 2)
        results[route] = jax.value_and_grad(f)(p, x)
        if route == "kernel":
            jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: f(p, x)))(p, x).jaxpr
            assert pallas_calls(jaxpr, "selective_scan_fwd") == 2
            assert pallas_calls(jaxpr, "selective_scan_bwd") == 1
    np.testing.assert_allclose(results["kernel"][0], results["plain"][0], rtol=1e-5)
    for leaf in ("mamba_in", "mamba_A_log", "mamba_dt_w", "mamba_x", "mamba_D"):
        want = np.asarray(results["plain"][1][leaf])
        np.testing.assert_allclose(results["kernel"][1][leaf], want, rtol=2e-3,
                                   atol=2e-5 * float(np.max(np.abs(want))))


def test_custom_model_ignores_the_harness_keys_and_trains():
    spec, trainer = lm.trainer(warmup_steps=1)
    model = zoo().custom_model(field_vocab="512", **lm.tiny_params(warmup_steps=1))
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
    assert std("embed") == pytest.approx(0.02, rel=0.1)
    assert std("mlp_gate_up") == pytest.approx(0.02, rel=0.1)
    assert std("attn_lambda") == pytest.approx(0.1, rel=0.2)
    np.testing.assert_allclose(np.exp(np.asarray(params["mamba_A_log"]))[0, 0],
                               np.arange(1, 17), rtol=1e-6)
    step = np.log1p(np.exp(np.asarray(params["mamba_dt_b"])))      # softplus of the bias
    assert np.all((1e-3 * 0.99 <= step) & (step <= 0.1 * 1.01))
    for leaf in ("ln1_bias", "ln2_bias", "final_norm_bias", "attn_qkv_b", "attn_wo_b", "cross_q_b"):
        assert not np.asarray(params[leaf]).any(), leaf
    for leaf in ("ln1_scale", "attn_subln", "mamba_D"):
        assert float(jnp.max(jnp.abs(params[leaf] - 1.0))) == 0.0, leaf


def test_published_defaults_count_the_uncut_model_s_parameters():
    model = zoo().custom_model()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes["params"]))
    assert flops.parameter_count("published") == count == 3_852_562_944      # the card's 3.8B
    cell = common.model_params(common.load_json("configs", "phi-4-mini-flash.json"))
    assert flops.parameter_count(cell) == 697_094_272
    cut = zoo().custom_model(**cell)
    shapes = jax.eval_shape(cut.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(shapes["params"])) == 697_094_272


# ------------------------------------------------------------------ #
# what the cell's check must catch (all of them, at full width:
# benchmark/rehearse/departures_phi4flash.py on the chip)

# float32 at the tiny preset: the program as it is agrees with the reference
# to rounding, so limits far under the chip's tell a departure at once
TIGHT = {"loss_rel": 1e-5, "mu_rel_l2": {"default": 1e-3}, "update_rel_l2": {"default": 0.1}}
CAUGHT = ("second_map_left_out", "memory_after_the_gate", "window_dropped",
          "head_share_of_the_tied_gradient_dropped",
          "shared_kv_cotangent_of_the_cross_layers_dropped")


def test_the_program_as_it_is_passes_the_tight_check(monkeypatch):
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    verdict = lm.run_check()
    assert verdict["ok"], verdict["failures"]


@pytest.mark.parametrize("name", CAUGHT)
def test_the_check_catches_a_departure(name, monkeypatch):
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    verdict = lm.run_check(name)
    assert not verdict["ok"] and verdict["failures"]


def test_every_departure_and_control_patches_something_the_program_has():
    names = {**departures.DEPARTURES, **departures.CONTROLS}
    assert set(CAUGHT) <= set(departures.DEPARTURES)
    for name, patch in names.items():
        for obj, attr, _ in patch(zoo(), jnp, jax):
            assert hasattr(obj, attr), (name, attr)
