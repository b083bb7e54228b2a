"""Mellum2 (model_zoo/transformer/mellum.py: sliding-window and full layers
under two rotary tables, grouped-query heads, a held share of softmax-routed
gated-SiLU experts with renormalised weights) against its plain reference
(benchmark/reference/mellum.py) on seeded weights, at a tiny size on the CPU:
hidden 48, four layers (three sliding with a window of 8, one full), 4/2
heads of 16, 16 experts top-3 of which experts 4-7 are held, vocabulary 256,
36 tokens, float32.

The comparison is the benchmark's own (`StatelessStepCheck` of
`benchmark/drivers/resident_lm_stateless.py` over `benchmark/check_lm.py`),
so the cases at the bottom hold it to its purpose: each departure the cell's
check must catch on the chip is patched into the program
(`benchmark/rehearse/departures_mellum.py`) and the comparison must FAIL.
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_lm, common
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.ops import pallas_attention
from elasticdl_tpu.parallel.mesh import build_mesh
from elasticdl_tpu.training.model_spec import ModelSpec
from elasticdl_tpu.training.trainer import Trainer
from tests.conftest import pallas_calls

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = common.load_json("rehearse", "tiny-lm-mellum.json")["model_params"]
LEAVES = ("embed", "final_norm", "head", "attn_norm", "wq", "wk", "wv", "wo",
          "moe_norm", "moe_router", "w_gate", "w_up", "w_down")
# float32 against float32: the only differences are the order of sums
TIGHT = {"loss_rel": 1e-5, "loss_ce_rel": 1e-5, "loss_aux_rel": 2e-4,
         "routing_agreement_min": 1.0,
         "router_same_input_agreement_min": 1.0, "router_weight_rel_median": 1e-5,
         "mu_rel_l2": {"default": 1e-4, "experts": 1e-4},
         "update_rel_l2": {"default": 2e-3, "experts": 2e-3}}

reference = common.load_module("reference", "mellum")
flops = common.load_module("flops", "mellum")
driver = common.load_module("drivers", "resident_lm_stateless")
departures = common.load_module("rehearse", "departures_mellum")


def tiny_params(**more):
    return {k: str(v) for k, v in {**TINY, **more}.items()}


def build_trainer(seed=0, **more):
    cfg = JobConfig.from_argv([
        "--model_zoo", os.path.join(ROOT, "model_zoo"),
        "--model_def", "transformer.mellum.custom_model",
        "--model_params", common.format_model_params(tiny_params(**more))])
    spec = ModelSpec.from_config(cfg)
    return spec, Trainer(spec, build_mesh(devices=jax.devices()[:1]), seed=seed)


def batches(steps=2, batch=2, seq=36, seed=1):
    toks = np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (steps, batch, seq + 1)).astype(np.int32)
    return [{"features": t[:, :-1], "labels": t[:, 1:],
             "mask": np.ones((batch,), np.float32)} for t in toks]


def zoo():
    return sys.modules["transformer.mellum"]


def lively(state, seed=5):
    """Parameters as a trained model has them rather than as the seed leaves
    them: router logits of order one, every norm's weight away from one,
    projections large enough that attention's softmax is far from a running
    mean, so that positions — the window and the two tables — matter."""
    r = np.random.default_rng(seed)
    p = dict(state.params)
    p["moe_router"] = p["moe_router"] * 8.0
    for name in LEAVES:
        if name.endswith("norm"):
            p[name] = p[name] * jnp.asarray(r.uniform(0.5, 1.5, p[name].shape), jnp.float32)
    for name in ("wq", "wk", "wv", "w_gate", "w_up"):
        p[name] = p[name] * 6.0
    for name in ("wo", "w_down"):       # what a sub-block writes is as large as
        p[name] = p[name] * 45.0        # the stream it writes to (embedding: one)
    return state.replace(params=p)


# the check's cases run ONE period of two layers (a sliding one, a full one):
# both kinds, and half the compile time of the tiny preset's four
SHORT = {"num_hidden_layers": 2, "sliding_period": 2}


def run_check(departure=None):
    """The benchmark's check, as `drivers/resident_lm_stateless.py` drives
    it, under the reference's `TOLERANCES` and `EXPERT_PAIRS_FLOOR` as the
    test has set them."""
    spec, trainer = build_trainer(**SHORT)
    data = batches()

    def fresh_state():
        return lively(trainer.init_state(data[0]))

    with departures.applied(departure, zoo()):
        return driver.program_check(trainer, spec, trainer.mesh, zoo(), reference,
                                    tiny_params(**SHORT), data, fresh_state,
                                    lambda text: None)


def program_terms(spec, params, batch):
    outputs, sown = spec.model.apply(
        {"params": params}, batch["features"], training=False, mutable=["losses", "router_state", "attn"])
    terms = {k: jnp.mean(v) for k, v in spec.loss(batch["labels"], outputs).items()}
    terms["loss_aux"] = sown["losses"]["load_balance"]
    terms["loss"] = terms["loss"] + terms["loss_aux"]
    return terms


@pytest.fixture(scope="module")
def gradients():
    """(program's, reference's) loss terms and gradients of one batch from
    the same lively parameters."""
    spec, trainer = build_trainer()
    batch = batches(steps=1)[0]
    params = lively(trainer.init_state(batch)).params

    def program_loss(p):
        terms = program_terms(spec, p, batch)
        return terms["loss"], terms

    hp = reference.hyper(tiny_params())
    ref_batch = {"tokens": batch["features"], "labels": batch["labels"],
                 "mask": batch["mask"]}

    def reference_loss(p):
        total, terms, _ = reference.loss_terms(p, ref_batch, hp)
        return total, terms

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(program_loss, has_aux=True))(params)
        want = jax.jit(jax.value_and_grad(reference_loss, has_aux=True))(params)
    return got, want


# ------------------------------------------------------------------ #
# the two rotary tables, by hand


def published():
    build_trainer()
    return zoo().Config()


def test_the_yarn_ramp_runs_from_dimension_18_to_35():
    cfg = published()
    d, theta = cfg.head_dim, cfg.rope_theta
    dim = lambda turns: d * math.log(8192 / (2 * math.pi * turns)) / (2 * math.log(theta))
    assert (round(dim(32), 2), round(dim(1), 2)) == (18.08, 34.98)
    assert (math.floor(dim(32)), math.ceil(dim(1))) == (18, 35)
    ratio = np.asarray(zoo().yarn_inv_freq(cfg)) / (theta ** (-np.arange(64) * 2.0 / d))
    np.testing.assert_allclose(ratio[:19], 1.0, rtol=1e-6)        # i <= 18 unscaled
    np.testing.assert_allclose(ratio[35:], 1 / 16, rtol=1e-6)     # i >= 35 divided by 16
    assert np.all(np.diff(ratio[18:36]) < 0)                      # the blend in between
    np.testing.assert_allclose(ratio[20], 1 - (2 / 17) * (15 / 16), rtol=1e-6)


def test_the_attention_factor_is_a_tenth_of_ln_16_plus_one():
    assert published().attention_factor == pytest.approx(0.1 * math.log(16) + 1, abs=1e-15)


@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_rotary_table_by_hand(kind):
    cfg = published()
    cos, sin = zoo().rotary_tables(cfg, 40)[kind]
    assert cos.shape == sin.shape == (1, 40, 1, 128)
    t, i = 37, 50                                  # a slow dimension: i >= 35
    freq = 500000.0 ** (-2 * i / 128)
    factor = 1.0
    if kind == "full":
        freq, factor = freq / 16, 1.2772588722239782
    for column in (i, i + 64):                     # rotate-half: both halves alike
        np.testing.assert_allclose(cos[0, t, 0, column], factor * math.cos(t * freq), rtol=1e-5)
        np.testing.assert_allclose(sin[0, t, 0, column], factor * math.sin(t * freq), rtol=1e-5)
    # a fast dimension keeps its frequency in both tables; the full one scales
    np.testing.assert_allclose(cos[0, 3, 0, 2], factor * math.cos(3 * 500000.0 ** (-4 / 128)),
                               rtol=1e-5)


def test_the_plain_table_is_olmoe_s_rope():
    from model_zoo.transformer.olmoe import rope

    cfg = published()
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 24, 2, 128)), jnp.float32)
    np.testing.assert_allclose(zoo().rotate(x, zoo().rotary_tables(cfg, 24)["sliding"]),
                               rope(x, cfg.rope_theta), rtol=1e-5, atol=1e-6)


def test_reference_tables_are_the_program_s():
    cfg = published()
    hp = reference.hyper({k: str(getattr(cfg, k)) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "sliding_window", "num_experts",
        "num_experts_per_tok", "moe_intermediate_size")})
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 33, 2, 128)), jnp.float32)
    tables = zoo().rotary_tables(cfg, 33)
    for kind in ("sliding", "full"):
        np.testing.assert_allclose(zoo().rotate(x, tables[kind]),
                                   reference.rotary(x, kind == "full", hp),
                                   rtol=2e-5, atol=2e-5)


def test_layers_are_three_sliding_to_one_full():
    cfg = published()
    kinds = [cfg.kind(l) for l in range(28)]
    assert kinds == ["sliding", "sliding", "sliding", "full"] * 7
    hp = {"sliding_period": 4}
    assert [reference.is_full(l, hp) for l in range(28)] == [k == "full" for k in kinds]


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
    assert check_lm._rel_l2(np.asarray(got[leaf]), np.asarray(want[leaf])) < 1e-4


def test_two_adamw_steps_match_reference(monkeypatch):
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = run_check()
    assert verdict["ok"], verdict["failures"]
    figures = verdict["figures"]
    assert figures["leaves_compared"] == len(LEAVES)
    assert figures["experts_compared"] == TINY["num_experts"]
    assert len(figures["router_same_input"]) == 2           # every step
    assert len(figures["loss_ce_program"]) == len(figures["loss_aux_reference"]) == 2
    assert figures["loss_ce_rel"] < 1e-5 and figures["loss_aux_rel"] < 2e-4


def test_the_window_and_the_tables_matter_at_the_tiny_size(gradients):
    """The comparison above would prove little if a full causal mask gave the
    same loss: with the window taken away the program's loss moves."""
    spec, trainer = build_trainer()
    batch = batches(steps=1)[0]
    params = lively(trainer.init_state(batch)).params
    plain = float(program_terms(spec, params, batch)["loss_ce"])
    with departures.applied("window_one_key_long", zoo()):
        longer = float(program_terms(spec, params, batch)["loss_ce"])
    assert abs(plain - longer) > 1e-4 * plain
    ((_, got), _), _ = gradients
    assert abs(plain - float(got["loss_ce"])) < 1e-6 * plain


def test_renormalised_top_k_weights_sum_to_one():
    spec, trainer = build_trainer()
    batch = batches(steps=1)[0]
    params = lively(trainer.init_state(batch)).params
    idx, weights, _ = zoo().expert_assignments(params, batch["features"], spec.model.cfg)
    assert idx.shape == weights.shape == (4, 2 * 36, 3)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    assert float(np.asarray(weights).min()) > 0


@pytest.mark.parametrize("route", ["fallback", "kernel"])
def test_keeping_the_flash_residuals_changes_no_value_on_the_cpu(route, monkeypatch):
    """`forward` checkpoints each layer with `KEEP_RESIDUALS`; on the kernel
    route (sequence 64, window 16: the banded kernels) a step's jaxpr holds
    ONE forward kernel a layer — three windowed, one full — and the plain
    policy two (counted in the jaxpr, not run); on the fallback route the
    gradients are equal either way to the bit."""
    if route == "kernel":
        monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
        monkeypatch.setenv("EDL_FLASH", "1")
    spec, trainer = build_trainer(sliding_window=16)
    batch = batches(steps=1, seq=64)[0]
    params = lively(trainer.init_state(batch)).params

    kept_policy = pallas_attention.KEEP_RESIDUALS

    def loss(keep):
        monkeypatch.setattr(pallas_attention, "KEEP_RESIDUALS",
                            kept_policy if keep else None)
        return lambda p: program_terms(spec, p, batch)["loss"]     # a new closure each time

    jaxpr = lambda keep: jax.make_jaxpr(jax.grad(loss(keep)))(params).jaxpr
    grads = lambda keep: jax.grad(loss(keep))(params)

    if route == "kernel":       # the calls are counted, not run: interpreted
        counts = lambda keep: [     # kernels under a gradient take minutes
            pallas_calls(jaxpr(keep), name) for name in (
                "flash_attention_swa_fwd", "flash_attention_fwd",
                "flash_attention_swa_bwd", "flash_attention_bwd")]
        assert counts(True) == [3, 1, 3, 1]
        assert counts(False) == [6, 2, 3, 1]
        return
    assert pallas_calls(jaxpr(True), "flash_attention_fwd") == 0
    kept, plain = grads(True), grads(False)
    for leaf in LEAVES:
        np.testing.assert_array_equal(np.asarray(kept[leaf]), np.asarray(plain[leaf]))


def test_the_program_counts_its_kernels_grid_steps(monkeypatch):
    """`attn/kv_block_visits` beside `attn/kv_block_visits_causal`, per kind
    [sliding, full], and the share of the pairs held, per layer (blocks of 16
    here, so that 64 tokens are four of them)."""
    for name in ("DEFAULT_BLOCK_Q", "DEFAULT_BLOCK_K"):
        monkeypatch.setattr(pallas_attention, name, 16)
    spec, trainer = build_trainer(sliding_window=16)
    batch = batches(steps=1, seq=64)[0]
    state = trainer.init_state(batch)
    for _ in range(2):
        state, _ = trainer.train_step(state, batch)
    banded, causal = pallas_attention.kv_block_visits(64, 64, 16, 16, jnp.float32)
    assert (banded, causal) == (7, 10)
    counted = jax.device_get(state.extra_vars)
    assert counted["attn"]["kv_block_visits"].tolist() == [2 * 3 * banded, 2 * causal]
    assert counted["attn"]["kv_block_visits_causal"].tolist() == [2 * 3 * causal, 2 * causal]
    share = counted["router_state"]["pairs_held_share"]
    assert share.shape == (4,) and np.all((share > 0) & (share < 1))
    assert counted["router_state"]["held_passes"].tolist() == [2, 2, 2, 2]


def test_custom_model_ignores_the_harness_keys_and_trains():
    spec, trainer = build_trainer(warmup_steps=1)
    model = zoo().custom_model(field_vocab="512", **tiny_params())
    assert model.cfg == spec.model.cfg
    data = batches(steps=1)[0]
    state = trainer.init_state(data)
    losses = []
    for _ in range(8):
        state, m = trainer.train_step(state, data)
        losses.append(float(m["loss"]))
    assert set(m) == {"loss", "loss_ce"} and float(m["loss"]) > float(m["loss_ce"])
    assert losses[-1] < losses[0] - 0.1     # an embedding of size one moves slowly


# ------------------------------------------------------------------ #
# parameter counts: the card's, and the cut's


def _published_params(**more):
    cfg = published()
    return {k: str(getattr(cfg, k)) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "sliding_window", "num_experts",
        "num_experts_per_tok", "moe_intermediate_size")} | {k: str(v) for k, v in more.items()}


@pytest.mark.parametrize("more,count", [
    ({}, 12_149_915_904),
    ({"num_hidden_layers": 4, "num_experts": 16, "router_experts": 64,
      "vocab_size": 24576}, 595_153_152)])
def test_parameter_count_uncut_and_at_the_cut(more, count):
    params = _published_params(**more)
    assert flops.parameter_count(params) == count
    model = zoo().custom_model(**params)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(shapes["params"])) == count


def test_the_card_s_active_parameters():
    assert flops.active_parameter_count(_published_params()) == 2_439_053_568


# ------------------------------------------------------------------ #
# the share of a deployment, tied to the whole (model-configs guide §4)


def test_four_held_shares_make_the_uncut_layer():
    """One feed-forward at 16 experts top-3: the parts that 4 shares of 4
    experts compute (the program's held dispatch) add up to what the
    reference gives for the layer with every expert held — as the cell's
    four shares of 16 make its 64 — and the reference, given a share, gives
    that share's part."""
    build_trainer()
    m = zoo()
    r = np.random.default_rng(3)
    c, f, e = 48, 24, 16
    normal = lambda *shape: r.normal(size=shape) * 0.2
    whole = {"moe_norm": r.uniform(0.5, 1.5, (c,)), "moe_router": r.normal(size=(c, e)),
             "w_gate": normal(e, c, f), "w_up": normal(e, c, f), "w_down": normal(e, f, c)}
    whole = {k: jnp.asarray(v, jnp.float32) for k, v in whole.items()}
    x = jnp.asarray(r.normal(size=(2, 9, c)), jnp.float32)
    hp_whole = reference.hyper(tiny_params(num_experts=16, first_expert=0))
    with jax.default_matmul_precision("highest"):
        want = reference.moe(whole, x, None, hp_whole)[0]
        total = jnp.zeros_like(x)
        for share in range(4):
            cfg = m.Config(**{**TINY, "first_expert": 4 * share})
            held = slice(4 * share, 4 * share + 4)
            part = {**whole, "w_gate": whole["w_gate"][held], "w_up": whole["w_up"][held],
                    "w_down": whole["w_down"][held]}
            y, _ = m.moe(part, x, cfg)
            total = total + y
            hp = reference.hyper(tiny_params(first_expert=4 * share))
            np.testing.assert_allclose(y, reference.moe(part, x, None, hp)[0],
                                       rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------------ #
# what the cell's check must catch, under the chip's own tolerances


@pytest.mark.parametrize("departure", [None] + sorted(departures.DEPARTURES))
def test_the_check_fails_on(departure, monkeypatch):
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = run_check(departure)
    assert verdict["ok"] == (departure is None), (verdict["failures"], verdict["figures"])


@pytest.mark.parametrize("control", sorted(departures.CONTROLS))
def test_a_precision_control_shows_in_the_figures(control, monkeypatch):
    """A part stated float32 kept in bfloat16 (the router's logits; the
    residual stream): here every matmul is float32, so the control alone
    makes the noise, and the float32-against-float32 limits must catch it."""
    monkeypatch.setattr(reference, "TOLERANCES", TIGHT)
    monkeypatch.setattr(reference, "EXPERT_PAIRS_FLOOR", 8)
    verdict = run_check(control)
    assert not verdict["ok"]
    assert any(f.startswith(("mu_rel_l2.", "router_")) for f in verdict["failures"]), \
        verdict["failures"]


def test_a_departure_s_trainer_does_not_get_another_s_compiled_step():
    """The departures' trainers take a program token of their own
    (`fresh_trainer`), else the second would be handed the first one's
    compiled, unpatched step."""
    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    config = {"model_def": "transformer.mellum.custom_model",
              "model_params": common.format_model_params(tiny_params())}
    data = batches(steps=1)[0]
    losses = {}
    for name in (None, "topk_weights_not_renormalised"):
        spec, mesh, trainer, module = departures.fresh_trainer(driver, config, 3)
        with departures.applied(name, module):
            state = lively(trainer.init_state(data))
            _, m = trainer.train_many(state, shard_batch_stack(
                mesh, [data], spec.batch_partition))
        losses[name] = float(m["loss_ce"][0])
    assert abs(losses[None] - losses["topk_weights_not_renormalised"]) > 1e-5
