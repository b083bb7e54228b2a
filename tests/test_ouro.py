"""Ouro (model_zoo/transformer/ouro.py: one stack of layers run several times
over shared weights, an exit after every pass through one head and one gate,
the entropy-regularised expected loss over the exits) against its plain
reference (benchmark/reference/ouro.py) on seeded weights, at a tiny size on
the CPU: hidden 48, two layers run three times, 4 heads of 16, an MLP of 96,
vocabulary 256, 40 tokens, float32.

The benchmark's own comparison, and the departures it must catch, are in
`tests/test_ouro_check.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from elasticdl_tpu.ops import pallas_attention
from tests import zoo_lm
from tests.conftest import pallas_calls

TINY = zoo_lm.preset("tiny-lm-ouro.json")
NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")
LEAVES = ("embed", "final_norm", "head", "exit_gate_w", "exit_gate_b",
          "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down") + NORMS
PASSES, LAYERS = TINY["total_ut_steps"], TINY["num_hidden_layers"]
TERMS = ["loss", "loss_expected", "loss_entropy"] + [
    f"loss_exit_{t + 1}" for t in range(PASSES)]
COUNTERS = ("loop", "exit", "attn")

reference = common.load_module("reference", "ouro")
flops = common.load_module("flops", "ouro")
driver = common.load_module("drivers", "resident_lm_dense")
departures = common.load_module("rehearse", "departures_ouro")

lm = zoo_lm.ZooLM(
    "ouro", tiny=TINY, reference=reference, driver=driver, departures=departures,
    seq=40, mutable=COUNTERS, training=True,
    # every norm's weight away from one, a gate that tells positions apart
    # (logits of order one: the exit distribution differs from position to
    # position and its entropy term has a gradient worth comparing), a bias
    # away from zero, sub-blocks whose output is not a rounding of the stream
    lively=[(NORMS + ("final_norm",), zoo_lm.jittered),
            (("exit_gate_w",), zoo_lm.scaled(8.0)),
            (("exit_gate_b",), lambda leaf, r: leaf + 0.3),
            (("wq", "wk", "wv", "w_gate", "w_up"), zoo_lm.scaled(6.0)),
            (("wo", "w_down"), zoo_lm.scaled(3.0))])


def zoo():
    return lm.zoo


@pytest.fixture(scope="module")
def gradients():
    """(program's, reference's) loss terms and gradients of one batch from
    the same lively parameters."""
    def reference_loss(p, batch, hp):
        total, terms, _ = reference.loss_terms(p, batch, hp)
        return total, {"loss": total, **terms}

    return lm.gradients(reference_loss)


# ------------------------------------------------------------------ #
# the model against the reference


@pytest.mark.parametrize("term", TERMS)
def test_loss_terms_match_reference(gradients, term):
    ((_, got), _), ((_, want), _) = gradients
    np.testing.assert_allclose(float(got[term]), float(want[term]), rtol=2e-6)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradients_match_reference(gradients, leaf):
    (_, got), (_, want) = gradients
    want_leaf = np.asarray(want[leaf])
    assert np.linalg.norm(want_leaf) > 0                 # every leaf is reached
    error = np.linalg.norm(np.asarray(got[leaf]) - want_leaf) / np.linalg.norm(want_leaf)
    assert error < 5e-5, error


def test_the_reference_blocks_change_no_value(monkeypatch):
    """Query blocks of 8 and row blocks of 10 against one block each."""
    batch = lm.batches(steps=1)[0]
    ref_batch = {"tokens": batch["features"], "labels": batch["labels"],
                 "mask": batch["mask"]}
    hp = reference.hyper(lm.tiny_params())
    whole = reference.loss_terms(lm.params(), ref_batch, hp)[0]
    monkeypatch.setattr(reference, "QUERY_BLOCK", 8)
    monkeypatch.setattr(reference, "ROW_BLOCK", 10)
    np.testing.assert_allclose(float(reference.loss_terms(lm.params(), ref_batch, hp)[0]),
                               float(whole), rtol=1e-6)


def test_the_reference_s_product_rounds_both_operands_and_the_cotangent():
    """With bfloat16 operands the reference's matmul is the configuration's:
    a, b and — in the backward pass — the cotangent rounded, every sum
    float32 and written float32; with float32 operands it is the plain
    product."""
    r = np.random.default_rng(3)
    a, b, g = (jnp.asarray(r.normal(size=shape), jnp.float32)
               for shape in ((5, 7), (7, 3), (5, 3)))
    rounded = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    out, pull = jax.vjp(lambda a, b: reference.product(
        "ij,jk->ik", a, b, {"matmul_operands": "bfloat16"}), a, b)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(out, rounded(a) @ rounded(b), rtol=1e-6)
    da, db = pull(g)
    np.testing.assert_allclose(da, rounded(g) @ rounded(b).T, rtol=1e-6)
    np.testing.assert_allclose(db, rounded(a).T @ rounded(g), rtol=1e-6)
    assert float(jnp.max(jnp.abs(out - a @ b))) > 1e-4
    plain = reference.product("ij,jk->ik", a, b, {"matmul_operands": "float32"})
    np.testing.assert_array_equal(plain, a @ b)


# ------------------------------------------------------------------ #
# the loop: tied weights against the same stack untied


def _untied_loss(copies, shared, batch, cfg):
    """The stack UNTIED: pass t runs its own copy of the layers' weights
    (`copies[k]`: (P, L, ...)); everything else as the program has it."""
    x = jnp.take(shared["embed"], batch["features"], axis=0)
    states = []
    for t in range(cfg.total_ut_steps):
        for l in range(cfg.num_hidden_layers):
            x = zoo().layer({k: copies[k][t, l] for k in zoo().LAYER_KEYS}, x, cfg)
        x = zoo().rmsnorm(x, shared["final_norm"], cfg.rms_norm_eps)
        states.append(x)
    states = jnp.stack(states)
    p = zoo().exit_distribution(zoo().exit_gates(shared, states))
    ce = zoo().exit_cross_entropies(states, shared["head"], batch["labels"])
    return jnp.mean(jnp.sum(p * ce, axis=0) - cfg.exit_entropy_coef * zoo().entropy(p))


@pytest.fixture(scope="module")
def tied_and_untied():
    spec, _ = lm.trainer()
    cfg, params, batch = spec.model.cfg, lm.params(), lm.batches(steps=1)[0]
    tied = jax.jit(jax.value_and_grad(
        lambda p: lm.terms(spec, p, batch)["loss"]))(params)
    copies = {k: jnp.stack([params[k]] * cfg.total_ut_steps) for k in zoo().LAYER_KEYS}
    untied = jax.jit(jax.value_and_grad(
        lambda c: _untied_loss(c, params, batch, cfg)))(copies)
    return tied, untied


def test_the_tied_stack_is_the_untied_stack_with_copied_weights(tied_and_untied):
    (tied, _), (untied, _) = tied_and_untied
    np.testing.assert_allclose(float(tied), float(untied), rtol=1e-6)


@pytest.mark.parametrize("leaf", [k for k in LEAVES if k not in (
    "embed", "final_norm", "head", "exit_gate_w", "exit_gate_b")])
def test_the_tied_gradient_is_the_sum_of_the_untied_copies(tied_and_untied, leaf):
    (_, tied), (_, untied) = tied_and_untied
    want = np.asarray(untied[leaf]).sum(axis=0)
    assert np.asarray(untied[leaf]).shape[0] == PASSES
    np.testing.assert_allclose(np.asarray(tied[leaf]), want,
                               rtol=1e-4, atol=1e-6 * np.abs(want).max())


# ------------------------------------------------------------------ #
# the exits


def test_the_exit_distribution_sums_to_one_and_is_the_survival_product():
    gates = jnp.asarray(np.random.default_rng(0).uniform(0.05, 0.95, (3, 2, 7)), jnp.float32)
    p = np.asarray(zoo().exit_distribution(gates))
    assert p.shape == (4, 2, 7) and p.min() > 0
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    g = np.asarray(gates)
    np.testing.assert_allclose(p[2], g[2] * (1 - g[0]) * (1 - g[1]), rtol=1e-6)
    np.testing.assert_allclose(p[3], (1 - g[0]) * (1 - g[1]) * (1 - g[2]), rtol=1e-6)


def test_a_certain_exit_has_no_entropy():
    p = jnp.asarray([[1.0, 0.25], [0.0, 0.75]], jnp.float32)
    h = np.asarray(zoo().entropy(p))
    np.testing.assert_allclose(h, [0.0, -(0.25 * np.log(0.25) + 0.75 * np.log(0.75))],
                               rtol=1e-6)
    assert np.all(np.isfinite(np.asarray(jax.grad(lambda p: zoo().entropy(p).sum())(p))))


def test_one_pass_is_the_plain_cross_entropy_with_no_gate_gradient():
    spec, _ = lm.fresh_trainer(total_ut_steps=1)
    cfg, batch = spec.model.cfg, lm.batches(steps=1)[0]
    params = lm.params()

    def program_loss(p):
        terms = lm.terms(spec, p, batch)
        return terms["loss"], terms

    (value, terms), grads = jax.jit(jax.value_and_grad(program_loss, has_aux=True))(params)
    assert sorted(terms) == ["loss", "loss_entropy", "loss_exit_1", "loss_expected"]
    assert float(terms["loss_entropy"]) == 0.0
    np.testing.assert_allclose(float(value), float(terms["loss_exit_1"]), rtol=1e-7)

    x = jnp.take(params["embed"], batch["features"], axis=0)
    for l in range(cfg.num_hidden_layers):
        x = zoo().layer({k: params[k][l] for k in zoo().LAYER_KEYS}, x, cfg)
    logits = zoo().rmsnorm(x, params["final_norm"], cfg.rms_norm_eps) @ params["head"]
    plain = -jnp.take_along_axis(jax.nn.log_softmax(logits), batch["labels"][..., None],
                                 axis=-1).mean()
    np.testing.assert_allclose(float(value), float(plain), rtol=1e-6)
    assert not np.any(np.asarray(grads["exit_gate_w"]))
    assert float(grads["exit_gate_b"]) == 0.0
    assert np.linalg.norm(np.asarray(grads["wq"])) > 0


# ------------------------------------------------------------------ #
# recomputation changes no value


def _gradients(spec):
    batch = lm.batches(steps=1)[0]
    loss = lambda p: lm.terms(spec, p, batch)["loss"]        # a new closure each time
    return jax.jit(jax.value_and_grad(loss))(lm.params())


def test_keeping_no_flash_residuals_changes_no_value(monkeypatch):
    spec, _ = lm.fresh_trainer()
    value, grads = _gradients(spec)
    monkeypatch.setattr(pallas_attention, "KEEP_RESIDUALS", None)
    other_value, other = _gradients(spec)
    np.testing.assert_allclose(float(other_value), float(value), rtol=1e-6)
    for leaf in LEAVES:
        np.testing.assert_allclose(np.asarray(other[leaf]), np.asarray(grads[leaf]),
                                   rtol=1e-4, atol=1e-6 * np.abs(np.asarray(grads[leaf])).max())


@pytest.mark.parametrize("kept", [True, False])
def test_kept_residuals_save_a_forward_kernel_an_application(kept, monkeypatch):
    """On the kernel route (128 tokens, heads of 128) a step's jaxpr holds ONE
    forward kernel for every application, whose flash residuals are kept, and
    two where the policy is taken away and the recomputation rebuilds them
    (counted in the jaxpr, not run)."""
    monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
    monkeypatch.setenv("EDL_FLASH", "1")
    if not kept:
        monkeypatch.setattr(pallas_attention, "KEEP_RESIDUALS", None)
    spec, trainer = lm.fresh_trainer(head_dim=128, num_attention_heads=1,
                                     num_key_value_heads=1)
    batch = lm.batches(steps=1, batch=1, seq=128)[0]
    params = trainer.init_state(batch).params
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: lm.terms(spec, p, batch)["loss"]))(params).jaxpr
    applications = PASSES * LAYERS
    assert pallas_calls(jaxpr, "flash_attention_bwd") == applications
    assert pallas_calls(jaxpr, "flash_attention_fwd") == (1 if kept else 2) * applications


# ------------------------------------------------------------------ #
# the departures the benchmark's check is held to (`tests/test_ouro_check.py`
# runs the check itself under two of them)

# departure -> (a term of the loss it moves or None, a leaf whose gradient it
# moves)
CHANGES = {
    "second_pass_left_out_of_the_shared_gradient": (None, "wq"),
    "norm_between_passes_left_out": ("loss_exit_2", "wq"),
    "entropy_sign_flipped": ("loss_entropy", "exit_gate_w"),
    "entropy_coef_doubled": ("loss_entropy", "exit_gate_w"),
    "gate_bias_left_out": ("loss_expected", "exit_gate_b"),
    "residual_stream_in_bfloat16": ("loss_exit_1", "wq"),
    "cross_entropy_from_bfloat16_logits": ("loss_exit_1", "head"),
}


@pytest.fixture(scope="module")
def as_it_is():
    spec, _ = lm.trainer()
    batch = lm.batches(steps=1)[0]

    def loss(p):
        terms = lm.terms(spec, p, batch)
        return terms["loss"], terms

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(lm.params())


@pytest.mark.parametrize("departure", sorted(CHANGES))
def test_a_departure_changes_what_the_check_compares(departure, as_it_is):
    assert set(CHANGES) == set(departures.DEPARTURES) | set(departures.CONTROLS)
    (_, terms), grads = as_it_is
    spec, _ = lm.fresh_trainer()
    batch = lm.batches(steps=1)[0]

    def loss(p):
        patched = lm.terms(spec, p, batch)
        return patched["loss"], patched

    with departures.applied(departure, zoo()):
        (_, patched), patched_grads = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(lm.params())
    term, leaf = CHANGES[departure]
    rel = lambda a, b: float(np.linalg.norm(np.asarray(a) - np.asarray(b))
                             / np.linalg.norm(np.asarray(b)))
    if term is None:        # the forward pass is the program's own
        assert all(float(patched[k]) == float(terms[k]) for k in terms)
    else:
        assert rel(patched[term], terms[term]) > 1e-6
    assert rel(patched_grads[leaf], grads[leaf]) > 1e-3


# ------------------------------------------------------------------ #
# counters, metrics, shape functions


def test_the_program_counts_its_loop_and_its_exits():
    spec, trainer = lm.trainer()
    state = lm.state()
    batches = lm.batches(steps=3)
    for batch in batches:
        state, _ = trainer.train_step(state, batch)
    counted = jax.device_get(state.extra_vars)
    assert int(counted["loop"]["layer_applications"]) == 3 * PASSES * LAYERS
    assert int(counted["loop"]["passes"]) == 3 * PASSES
    pmf = np.asarray(counted["exit"]["pmf"])
    assert pmf.shape == (PASSES,) and abs(pmf.sum() - 1.0) < 1e-5
    assert 0.0 < float(counted["exit"]["entropy"]) <= np.log(PASSES) + 1e-6
    visits, causal = pallas_attention.kv_block_visits(40, 40, None, 16, jnp.float32)
    assert int(counted["attn"]["kv_block_visits"]) == 3 * PASSES * LAYERS * visits
    assert int(counted["attn"]["kv_block_visits_causal"]) == 3 * PASSES * LAYERS * causal


def test_evaluation_reads_the_last_exit_and_the_mean_exit_distribution():
    spec, trainer = lm.trainer()
    state, batch = lm.state(), lm.batches(steps=1)[0]
    results = trainer.metric_results(trainer.eval_step(
        state, batch, trainer.new_metric_states()))
    shares = [results[f"exit_share_{t + 1}"] for t in range(4)]
    assert abs(sum(shares) - 1.0) < 1e-5 and shares[PASSES:] == [0.0] * (4 - PASSES)
    outputs = spec.model.apply({"params": state.params, **state.extra_vars},
                               batch["features"], training=False)
    logits = zoo().head_logits(outputs["states"][-1], outputs["head"])
    want = float(np.mean(np.argmax(np.asarray(logits), -1) == batch["labels"]))
    assert results["token_accuracy"] == pytest.approx(want, abs=1e-6)
    # the loss an evaluation reports is the step's own
    assert results["loss"] == pytest.approx(
        float(lm.program_terms()(state.params, batch, state.extra_vars)["loss"]), rel=1e-5)


def test_unknown_keys_are_ignored_and_published_ones_taken():
    model = zoo().custom_model(total_ut_steps="2", rope_theta="500", minibatch_size="7",
                               exit_entropy_coef="0.05")
    assert (model.cfg.total_ut_steps, model.cfg.rope_theta,
            model.cfg.exit_entropy_coef) == (2, 500.0, 0.05)
    assert zoo().Config().num_hidden_layers == 48 and zoo().Config().total_ut_steps == 4
    with pytest.raises(ValueError):
        zoo().Config(total_ut_steps=0)


def test_shape_functions_count_every_application_once_a_pass():
    published = {"vocab_size": 49152, "hidden_size": 2048, "num_hidden_layers": 8,
                 "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128,
                 "intermediate_size": 5632, "total_ut_steps": 4}
    assert flops.parameter_count(published) == 612_438_017
    assert flops.parameter_count({**published, "num_hidden_layers": 48}) == 2_667_974_657
    shape = flops.shape(published, 1, 4096)
    assert shape["layer_applications_per_step"] == 32
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert shape["ut_attn_matmul_flops_per_step"] + shape["ut_mlp_matmul_flops_per_step"] \
        == 6.0 * layer * 4096 * 32
    assert shape["ut_exit_flops_per_step"] == 6.0 * 2048 * 49152 * 4096 * 4
    one = flops.shape({**published, "total_ut_steps": 1}, 1, 4096)
    assert shape["ut_attention_flops_per_step"] == 4 * one["ut_attention_flops_per_step"]
    assert shape["parameters"] == one["parameters"]       # swept once, used four times
    # the program's own parameters are the count's
    spec, trainer = lm.trainer()
    leaves = jax.tree_util.tree_leaves(lm.params())
    assert sum(x.size for x in leaves) == flops.parameter_count(lm.tiny_params())
