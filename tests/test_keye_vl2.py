"""Keye-VL-2.0's language model (model_zoo/transformer/keye_vl2.py: a learned
selection of keys — a lightning indexer, the K best keys of a query's causal
prefix, the indexer's own KL loss — over grouped-query heads, a held share of
softmax-routed gated-SiLU experts) against its plain reference
(benchmark/reference/keye_vl2.py) on seeded weights, at a tiny size on the CPU:
hidden 48, two layers, 4/2 heads of 16, 3 index heads of 8, 8 keys a query of
40, 16 experts top-3 of which experts 4-7 are held, vocabulary 256, float32.

The benchmark's own comparison, and the departures it must catch, are in
`tests/test_keye_vl2_check.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from elasticdl_tpu.ops import pallas_attention, sparse_attention
from tests import zoo_lm
from tests.conftest import equations, matmuls, pallas_calls, scans_with

TINY = zoo_lm.preset("tiny-lm-keye.json")
INDEX = ("index_wq", "index_wk", "index_k_scale", "index_k_bias", "index_w")
REST = ("embed", "final_norm", "head", "attn_norm", "wq", "wk", "wv", "wo", "q_norm",
        "k_norm", "moe_norm", "moe_router", "w_gate", "w_up", "w_down")
reference = common.load_module("reference", "keye_vl2")
flops = common.load_module("flops", "keye_vl2")

lm = zoo_lm.ZooLM(
    "keye_vl2", tiny=TINY, reference=reference, seq=40,
    driver=common.load_module("drivers", "resident_lm_dsa"),
    departures=common.load_module("rehearse", "departures_keye_vl2"),
    mutable=("losses", "router_state", "dsa"),
    sown={"loss_balance": "load_balance", "loss_index": "index_kl"},
    # router logits and index scores of order one, every norm's weight away
    # from one (the index keys' bias away from zero), projections large enough
    # that attention's softmax is far from a running mean
    lively=[(("moe_router",), zoo_lm.scaled(8.0)),
            (("attn_norm", "final_norm", "index_k_scale", "k_norm", "moe_norm", "q_norm"),
             zoo_lm.jittered),
            (("index_k_bias",), zoo_lm.drawn(0.3)),
            (("wq", "wk", "wv", "w_gate", "w_up", "index_wq", "index_wk"), zoo_lm.scaled(6.0)),
            (("index_w",), zoo_lm.scaled(30.0)),
            (("wo", "w_down"), zoo_lm.scaled(45.0))],
    # the check's cases run ONE layer: every mechanism, and half the compile
    # time of the preset's two
    short={"num_hidden_layers": 1})


def zoo():
    return lm.zoo


@pytest.fixture(scope="module")
def case():
    spec, trainer = lm.trainer()
    return spec, trainer, lm.batches(steps=1)[0], lm.params()


@pytest.fixture(scope="module")
def gradients(case):
    """(program's, reference's) loss terms and gradients of one batch from
    the same lively parameters, and the program's gradients of the index loss
    alone and of the rest alone."""
    spec, _, batch, params = case
    got, want = lm.gradients(lambda p, batch, hp: reference.loss_terms(p, batch, hp)[:2])
    with jax.default_matmul_precision("highest"):
        index_alone = jax.jit(jax.grad(
            lambda p: lm.terms(spec, p, batch)["loss_index"]))(params)
        rest_alone = jax.jit(jax.grad(lambda p: (lambda t: t["loss_ce"] + t["loss_balance"])(
            lm.terms(spec, p, batch))))(params)
    return got, want, index_alone, rest_alone


def test_the_three_terms_are_the_reference_s(gradients):
    (got, got_terms), _ = gradients[0]
    (want, want_terms), _ = gradients[1]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for name in ("loss_ce", "loss_balance", "loss_index"):
        np.testing.assert_allclose(got_terms[name], want_terms[name], rtol=2e-5, err_msg=name)
    # the selection selects: most rows keep 8 of up to 40 keys, and the
    # indexer is far from the attention it should imitate
    assert float(want_terms["loss_index"]) > 0.1


@pytest.mark.parametrize("leaf", INDEX + REST)
def test_every_gradient_is_the_reference_s(gradients, leaf):
    got, want = gradients[0][1][leaf], gradients[1][1][leaf]
    assert float(jnp.linalg.norm(want)) > 1e-6
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 2e-5


def test_the_two_gradient_paths_do_not_touch(gradients):
    """The indexer's four parameters receive gradient from the index loss
    ALONE, and every other parameter receives none from it."""
    _, _, index_alone, rest_alone = gradients
    for leaf in INDEX:
        assert float(jnp.abs(index_alone[leaf]).max()) > 1e-7, leaf
        assert float(jnp.abs(rest_alone[leaf]).max()) == 0.0, leaf
    for leaf in REST:
        assert float(jnp.abs(index_alone[leaf]).max()) == 0.0, leaf
        assert float(jnp.abs(rest_alone[leaf]).max()) > 1e-7, leaf


def test_the_step_reports_each_sown_term_by_name(case):
    spec, trainer, batch, _ = case
    state = trainer.init_state(batch)
    state, metrics = trainer.train_step(state, batch)
    metrics = {k: float(v) for k, v in metrics.items()}
    assert sorted(metrics) == ["loss", "loss_balance", "loss_ce", "loss_index"]
    assert metrics["loss_balance"] > 0 and metrics["loss_index"] > 0
    assert metrics["loss"] == pytest.approx(
        metrics["loss_ce"] + metrics["loss_balance"] + metrics["loss_index"], rel=1e-6)


def _selections_in(jaxpr):
    """`select`'s tie rule is the model's only `cond` that gives a boolean
    plane (the held dispatch's backward holds one a layer around its
    overflow's float32 sums): one a run of it."""
    return equations(jaxpr, lambda eqn: eqn.primitive.name == "cond"
                     and eqn.outvars[0].aval.dtype == jnp.bool_)


def _gradient_under(policy, spec, batch, params, run=True):
    """(jaxpr, gradients) of the batch's loss with every layer recomputed
    under `policy` in `KEEP_SELECTION`'s place."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sparse_attention, "KEEP_SELECTION", policy)
        loss = lambda p: lm.terms(spec, p, batch)["loss"]      # a new closure each time
        return (jax.make_jaxpr(jax.grad(loss))(params).jaxpr,
                jax.jit(jax.grad(loss))(params) if run else None)


@pytest.fixture(scope="module")
def recomputations(case):
    """The two-layer gradient under `KEEP_SELECTION` and under the flash
    kernels' policy alone, which knows neither the selection's names nor the
    index loss's."""
    spec, _, batch, params = case
    return (_gradient_under(sparse_attention.KEEP_SELECTION, spec, batch, params),
            _gradient_under(pallas_attention.KEEP_RESIDUALS, spec, batch, params))


def test_thresholds_and_keep_are_kept_across_the_recomputation(recomputations):
    """Under `KEEP_SELECTION` a layer's backward pass holds no second search:
    two layers, two `select`s in the whole gradient; under the flash kernels'
    policy alone, four. The values are the same where recomputing is exact."""
    (kept, a), (flash_only, b) = recomputations
    assert (_selections_in(kept), _selections_in(flash_only)) == (2, 4)
    for leaf in INDEX + REST:
        np.testing.assert_array_equal(np.asarray(a[leaf]), np.asarray(b[leaf]))


def _index_losses_in(jaxpr, batch, seq):
    """(evaluations of the index loss's target — the scans whose body holds
    the 4 heads' q·kᵀ of a block of rows, which nothing else makes —, the
    matmuls of the 3 index heads' scores in those bodies)."""
    rows = sparse_attention._rows(seq, sparse_attention.KL_ROWS)
    target, scores = (batch, 2, 2, rows, seq), (batch, 3, rows, seq)
    bodies = scans_with(jaxpr, lambda body: matmuls(body, target))
    assert all(matmuls(body, target) == 1 for body in bodies)
    return len(bodies), sum(matmuls(body, scores) for body in bodies)


def test_the_index_loss_is_evaluated_once_a_layer(recomputations, case):
    """Under `KEEP_SELECTION` the recomputed half of the gradient holds nothing
    of the index loss — its three gradients come from the forward pass by name:
    two layers, two evaluations of a block's target and of its scores; under
    the flash kernels' policy alone, four: the loss in the forward pass (the
    pull-back is dead code there: two kernels, not four, at a size that tiles)
    and the whole forward rule again in the recomputation. The values are
    `recomputations`' — equal, to the bit."""
    spec, _, batch, params = case
    (kept, _), (flash_only, _) = recomputations
    size = batch["features"].shape
    assert _index_losses_in(kept, *size) == (2, 2)
    assert _index_losses_in(flash_only, *size) == (4, 4)
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv(pallas_attention._INTERPRET_ENV, "1")
        tiles, _ = _gradient_under(pallas_attention.KEEP_RESIDUALS, spec,
                                   lm.batches(steps=1, seq=128)[0], params, run=False)
    assert _index_losses_in(tiles, size[0], 128) == (4, 4)
    assert pallas_calls(tiles, "index_score_bwd") == 2


@pytest.fixture(scope="module")
def pull_back_routes(case):
    """(jaxpr, gradients) of a batch of 128 tokens — one block of `KL_ROWS`
    rows against one key tile — with the index loss's pull-back as
    `jax.vjp(_score_block)` (a plain CPU) and as the kernel `index_score_bwd`
    (the interpret signal, which also sends the held experts through their
    grouped-matmul kernel)."""
    spec, _, _, params = case
    batch = lm.batches(steps=1, seq=128)[0]

    def traced_and_run():
        loss = lambda p: lm.terms(spec, p, batch)["loss"]      # a new closure each time
        return jax.make_jaxpr(jax.grad(loss))(params).jaxpr, jax.jit(jax.grad(loss))(params)

    plain = traced_and_run()
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv(pallas_attention._INTERPRET_ENV, "1")
        return plain, traced_and_run()


def test_the_model_s_backward_holds_the_pull_back_kernel_once_a_layer(pull_back_routes):
    """Two layers: two `index_score_bwd` on the kernel's route, none on a
    plain CPU, and on both ONE `select` a layer under `KEEP_SELECTION`."""
    (plain, _), (kernel, _) = pull_back_routes
    assert pallas_calls(plain, "index_score_bwd") == 0
    assert pallas_calls(kernel, "index_score_bwd") == 2
    assert (_selections_in(plain), _selections_in(kernel)) == (2, 2)
    # each beside the one evaluation of its layer's loss
    assert _index_losses_in(plain, 2, 128) == _index_losses_in(kernel, 2, 128) == (2, 2)


@pytest.mark.parametrize("leaf", INDEX)
def test_the_indexer_learns_the_same_by_either_pull_back(pull_back_routes, leaf):
    (_, plain), (_, kernel) = pull_back_routes
    assert float(jnp.linalg.norm(plain[leaf])) > 1e-6
    assert float(jnp.linalg.norm(kernel[leaf] - plain[leaf])
                 / jnp.linalg.norm(plain[leaf])) < 2e-5


def test_the_table_built_by_mrope_sections_is_the_plain_one():
    """A text sequence's three position components are equal, so the table
    the reference builds BY `mrope_section` from a (3, T) position array is
    the program's plain table — and is not, as soon as a component differs."""
    hp = reference.hyper(lm.tiny_params(head_dim=128, rope_theta=10000000))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 24, 2, 128)), jnp.float32)
    positions = reference.text_positions(24)
    assert positions.shape == (3, 24)
    plain = zoo().rotate(x, zoo().rotary_table(10000000.0, 128, 24))
    np.testing.assert_allclose(reference.rotary(x, positions, hp), plain, rtol=1e-5, atol=1e-5)
    # sections [16, 24, 24] of the 64 pairs: pair 20 follows the height
    moved = positions.at[1].add(3.0)
    angles = reference.mrope_angles(moved, 128, hp) - reference.mrope_angles(positions, 128, hp)
    assert np.flatnonzero(np.abs(np.asarray(angles)).max(0) > 0).tolist() == list(range(16, 40))
    # the index heads' 32 pairs split in proportion: 8, 12, 12
    angles = reference.mrope_angles(moved, 64, hp) - reference.mrope_angles(positions, 64, hp)
    assert np.flatnonzero(np.abs(np.asarray(angles)).max(0) > 0).tolist() == list(range(8, 20))


def test_the_program_counts_its_selection(case):
    spec, trainer, batch, _ = case
    state = trainer.init_state(batch)
    for _ in range(2):
        state, _ = trainer.train_step(state, batch)
    counted = jax.device_get(state.extra_vars)["dsa"]
    selected = 2 * sum(min(t + 1, 8) for t in range(40))
    assert counted["selected_pairs"].tolist() == [selected, selected]
    assert counted["causal_pairs"].tolist() == [2 * 40 * 41 // 2] * 2
    assert counted["causal_blocks"].tolist() == [2 * 5 * 6 // 2] * 2   # blocks of 8 here
    assert np.all(counted["live_blocks"] <= counted["causal_blocks"])
    assert counted["tie_rows"].shape == (2,)
    share = jax.device_get(state.extra_vars)["router_state"]["pairs_held_share"]
    assert share.shape == (2,) and np.all((share > 0) & (share < 1))


def test_selections_are_what_the_forward_pass_selected(case):
    spec, _, batch, params = case
    cfg = spec.model.cfg
    layer_input, threshold, keep = jax.jit(
        lambda p, toks: zoo().selections(p, toks, cfg))(params, batch["features"])
    assert layer_input.shape == (2, 2, 40, 48) and threshold.shape == (2, 2, 40)
    assert keep.shape == (2, 2, 40, 40) and keep.dtype == jnp.int8
    for layer in range(2):
        plane = zoo().index_plane({k: params[k][layer] for k in zoo().LAYER_KEYS},
                                  layer_input[layer], cfg)
        want = reference.own_selection(plane, 8)
        np.testing.assert_array_equal(np.asarray(keep[layer]) != 0, np.asarray(want))


def test_custom_model_ignores_the_harness_keys():
    spec, _ = lm.trainer()
    assert zoo().custom_model(field_vocab="512", **lm.tiny_params()).cfg == spec.model.cfg


@pytest.mark.parametrize("more,count", [
    ({}, 465_391_104),
    ({"num_hidden_layers": "48", "num_experts": "128", "vocab_size": "151936"}, 30_640_656_384),
])
def test_parameter_count_at_the_cut_and_uncut(more, count):
    config = common.load_json("configs", "keye-vl-2.0-30b-a3b.json")
    assert flops.parameter_count({**common.model_params(config), **more}) == count


def test_the_program_holds_the_parameters_the_shape_functions_count(case):
    params = case[3]
    assert sum(int(np.prod(v.shape)) for v in params.values()) \
        == flops.parameter_count(lm.tiny_params())


# the share of a deployment, tied to the whole (model-configs guide §4)


def test_eight_held_shares_make_the_uncut_layer():
    """One layer at 16 experts top-3: the expert outputs of 8 shares of 2
    experts (the program's held dispatch) add up to what the reference gives
    for the layer with every expert held; the attention sub-block and the
    index loss, which every chip computes alike, are counted once."""
    r = np.random.default_rng(3)
    m = zoo()
    whole = {k: v[0] for k, v in lm.params(
        num_hidden_layers=1, num_experts=16, first_expert=0, router_experts=16).items()
        if k in m.LAYER_KEYS}
    x = jnp.asarray(r.normal(size=(2, 40, 48)), jnp.float32)
    hp_whole = reference.hyper(lm.tiny_params(num_experts=16, first_expert=0))
    cfg_whole = m.Config(**{**TINY, "num_experts": 16, "first_expert": 0})
    tables = m.rotary_tables(cfg_whole, 40)
    with jax.default_matmul_precision("highest"):
        update, index_kl, _, _ = jax.jit(
            lambda p, x: reference.attention(p, x, None, hp_whole))(whole, x)
        mid = x + update
        want = mid + jax.jit(lambda p, x: reference.moe(p, x, None, hp_whole)[0])(whole, mid)
        ours, stats = jax.jit(lambda p, x: m.attention(p, x, tables, cfg_whole))(whole, x)
        np.testing.assert_allclose(ours, update, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(stats["index_kl"], index_kl, rtol=1e-5)
        total = mid                                 # what every chip computes alike, once
        for share in range(8):
            cfg = m.Config(**{**TINY, "num_experts": 2, "first_expert": 2 * share})
            held = slice(2 * share, 2 * share + 2)
            part = {**whole, "w_gate": whole["w_gate"][held], "w_up": whole["w_up"][held],
                    "w_down": whole["w_down"][held]}
            total = total + jax.jit(lambda p, x: m.moe(p, x, cfg)[0])(part, mid)
    assert float(jnp.abs(want - mid).max()) > 0.1
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
