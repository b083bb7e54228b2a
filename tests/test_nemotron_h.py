"""Nemotron-H (model_zoo/transformer/nemotron_h.py, ops/ssm.py, the held
dispatch of ops/moe.py) against its plain reference
(benchmark/reference/nemotron_h.py) on seeded weights, at a tiny size on the
CPU: hidden 48, pattern ME*ME, 8 Mamba heads of 8 with 2 groups and a
16-column state in chunks of 8, 4 query heads on 2 key-value heads, 16 experts
top-3 of which experts 4-7 are held, vocabulary 256, 36 tokens, float32.

The benchmark's own comparison, and the departures it must catch, are in
`tests/test_nemotron_h_check.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_lm, common
from elasticdl_tpu.ops import ssm
from tests import zoo_lm
from tests.conftest import pallas_calls

TINY = zoo_lm.preset("tiny-lm-share.json")
LEAVES = ("embed", "final_norm", "head",
          "mamba_norm", "mamba_in_proj", "mamba_conv_w", "mamba_conv_b",
          "mamba_dt_bias", "mamba_A_log", "mamba_D", "mamba_gate_norm", "mamba_out_proj",
          "moe_norm", "moe_router", "shared_up", "shared_down", "w_up", "w_down",
          "attn_norm", "attn_wq", "attn_wk", "attn_wv", "attn_wo")

reference = common.load_module("reference", "nemotron_h")
driver = common.load_module("drivers", "resident_lm_share")
departures = common.load_module("rehearse", "departures_nemotron_h")

lm = zoo_lm.ZooLM(
    "nemotron_h", tiny={**TINY, "conv_kernel": 4}, reference=reference, driver=driver,
    departures=departures, seq=36, mutable=("router_state",), training=True,
    # router logits of order one (as at the published width, 2688-wide tokens
    # against normal(0.02) weights), D, the norms' weights and the
    # convolution's bias away from their constants
    lively=[(("moe_router",), zoo_lm.scaled(8.0)),
            (("mamba_D", "mamba_gate_norm", "mamba_norm", "moe_norm", "attn_norm",
              "final_norm"), zoo_lm.jittered),
            (("mamba_in_proj", "mamba_out_proj", "attn_wq", "attn_wk", "attn_wv",
              "attn_wo", "shared_up", "shared_down", "w_up", "w_down"), zoo_lm.scaled(6.0))],
    # the check's cases run ONE layer of each kind, three for the preset's five
    short={"num_hidden_layers": 3, "hybrid_override_pattern": "ME*"})
# a selection bias that is not zero
BIAS = {"router_state": {"e_score_correction_bias": jnp.asarray(
    np.random.default_rng(2).normal(size=(2, 16)) * 0.02, jnp.float32)}}


def zoo():
    return lm.zoo


@pytest.fixture(scope="module")
def gradients():
    """(program's, reference's) loss and gradients of one batch from the
    same lively parameters and a selection bias that is not zero."""
    bias = BIAS["router_state"]["e_score_correction_bias"]
    ((got, _), got_grads), ((want, _), want_grads) = lm.gradients(
        lambda p, batch, hp: (reference.loss(p, batch, hp, None, bias)[0], {}), BIAS)
    return (got, got_grads), (want, want_grads)


def test_loss_matches_reference(gradients):
    (got, _), (want, _) = gradients
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    assert float(want) > np.log(TINY["vocab_size"]) - 0.5          # untrained


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_reference(gradients, leaf):
    (_, got), (_, want) = gradients
    assert set(got) == set(LEAVES)
    assert np.linalg.norm(want[leaf]) > 0
    assert check_lm._rel_l2(np.asarray(got[leaf]), np.asarray(want[leaf])) < 1e-4


def test_bias_update_by_hand():
    cfg = zoo().Config(**{k: v for k, v in TINY.items()})
    idx = jnp.asarray([[[0, 1, 2], [0, 1, 3], [0, 4, 5], [0, 1, 6]]] * 2, jnp.int32)
    bias = jnp.zeros((2, 16), jnp.float32).at[0, 0].set(0.5)
    got = np.asarray(zoo().updated_bias(bias, idx, cfg))
    load = np.bincount(np.asarray(idx[0]).ravel(), minlength=16)      # mean 0.75
    want = np.where(load > 0.75, -1e-3, 1e-3).astype(np.float32)
    np.testing.assert_allclose(got[1], want, atol=1e-9)
    np.testing.assert_allclose(got[0, 0], 0.5 - 1e-3, atol=1e-7)
    want_ref = np.asarray(reference.bias_update(
        bias, jnp.asarray(check_lm.chosen_mask(idx, 16))))
    np.testing.assert_allclose(got, want_ref, atol=0)


def test_eval_leaves_the_bias_alone_and_training_moves_it():
    spec, trainer = lm.trainer()
    data = lm.batches(steps=1)[0]
    state = trainer.init_state(data)
    bias = lambda s: np.asarray(s.extra_vars["router_state"]["e_score_correction_bias"])
    assert bias(state).shape == (2, 16) and not bias(state).any()
    spec.model.apply({"params": state.params, **state.extra_vars}, data["features"],
                     training=False)                       # no mutable collection: must not write
    state, _ = trainer.train_step(state, data)
    assert np.allclose(np.abs(bias(state)), 1e-3)


@pytest.mark.parametrize("pass_rows", [8, 24])
def test_several_passes_a_layer_give_the_same_step_and_are_counted(pass_rows, monkeypatch):
    """A pass made smaller than the pairs on held experts: the step's loss
    and parameters are those of one pass a layer, and `router_state/
    held_passes` holds ceil(pairs on held experts / pass) of every E layer,
    `router_state/held_row_tiles` the row tiles the grouped matmul's own
    metadata counts for those passes, `router_state/held_row_chunks` the
    chunks of those passes that hold a held pair."""
    from elasticdl_tpu.ops import moe as moe_ops
    from elasticdl_tpu.ops import pallas_gmm

    data = lm.batches(steps=1)[0]

    def one_step(trainer_of):
        spec, trainer = trainer_of(warmup_steps=1)
        state, m = trainer.train_step(lm.state(warmup_steps=1), data)
        counters = state.extra_vars[reference.PASSES[0]]
        return (float(m["loss"]), jax.device_get(state.params),
                np.asarray(counters[reference.PASSES[1]]),
                np.asarray(counters["held_row_tiles"]),
                np.asarray(counters["held_row_chunks"]), spec.model.cfg)

    def row_tiles(on_held, rows):
        """What the kernel's own metadata counts for each pass's rows."""
        tm = pallas_gmm.row_tile(rows)
        return [sum(int(pallas_gmm.row_tile_visits(
            jnp.asarray([min(max(held - lo, 0), rows)], jnp.int32), rows, tm).row_tiles)
            for lo in range(0, idx[0].size, rows)) for held in on_held]

    def row_chunks(on_held, rows):
        chunk = moe_ops.held_row_chunk(rows)
        return [sum(-(-min(max(held - lo, 0), rows) // chunk)
                    for lo in range(0, idx[0].size, rows)) for held in on_held]

    idx = np.asarray(lm.assignments(warmup_steps=1)(
        lm.params(warmup_steps=1), jnp.zeros((2, 16)), data["features"])[0])
    loss_one, params_one, passes_one, tiles_one, chunks_one, cfg = one_step(lm.trainer)
    on_held = np.sum((idx >= 4) & (idx < 8), axis=(1, 2))
    assert on_held.min() > pass_rows
    np.testing.assert_array_equal(passes_one, [1, 1])
    one_pass = moe_ops.held_pass_rows(idx[0].size, cfg.num_experts, cfg.held[1])
    np.testing.assert_array_equal(tiles_one, row_tiles(on_held, one_pass))
    np.testing.assert_array_equal(chunks_one, row_chunks(on_held, one_pass))
    monkeypatch.setattr(moe_ops, "held_pass_rows", lambda pairs, e, count: pass_rows)
    loss_many, params_many, passes_many, tiles_many, chunks_many, _ = one_step(
        lm.fresh_trainer)
    np.testing.assert_array_equal(passes_many, -(-on_held // pass_rows))
    np.testing.assert_array_equal(tiles_many, row_tiles(on_held, pass_rows))
    np.testing.assert_array_equal(chunks_many, row_chunks(on_held, pass_rows))
    np.testing.assert_allclose(loss_many, loss_one, rtol=1e-6)
    for name in LEAVES:
        np.testing.assert_allclose(params_many[name], params_one[name], rtol=1e-4,
                                   atol=1e-6, err_msg=name)


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


def test_pattern_must_spell_the_layers():
    with pytest.raises(ValueError, match="does not spell"):
        zoo().Config(num_hidden_layers=4, hybrid_override_pattern="MEM")
    with pytest.raises(ValueError, match="does not spell"):
        zoo().Config(num_hidden_layers=3, hybrid_override_pattern="MXM")


def test_published_defaults_count_the_card_s_parameters():
    cfg = zoo().Config()
    assert (cfg.layers_of("M"), cfg.layers_of("E"), cfg.layers_of("*")) == (23, 23, 6)
    assert cfg.d_inner == 4096 and cfg.conv_dim == 6144 and cfg.num_experts == 128
    assert cfg.held == (0, 128)


# ------------------------------------------------------------------ #
# ops/ssm.py, each against a form written by hand


def token_by_token(x, dt, a, b, c):
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    y = np.zeros((bsz, t, h, p))
    for i in range(bsz):
        state = np.zeros((h, p, n))
        for s in range(t):
            for head in range(h):
                grp = head // (h // g)
                state[head] = np.exp(dt[i, s, head] * a[head]) * state[head] \
                    + dt[i, s, head] * np.outer(x[i, s, head], b[i, s, grp])
                y[i, s, head] = state[head] @ c[i, s, grp]
    return y


def scan_inputs(t, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(2, t, 4, 3))
    dt = np.log1p(np.exp(r.normal(size=(2, t, 4))))
    a = -np.exp(r.normal(size=(4,)))
    b, c = r.normal(size=(2, t, 2, 5)), r.normal(size=(2, t, 2, 5))
    return [np.asarray(v, np.float32) for v in (x, dt, a, b, c)]


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("tokens,chunk", [(32, 8), (29, 8), (7, 16), (16, 16)])
def test_ssd_chunked_matches_the_recurrence(tokens, chunk, direction):
    """Chunk sizes that divide the sequence, that leave a tail, and that are
    longer than it."""
    args = scan_inputs(tokens)
    chunked = lambda *v: ssm.ssd_chunked(*v, chunk, jnp.float32)
    with jax.default_matmul_precision("highest"):
        if direction == "forward":
            np.testing.assert_allclose(chunked(*args), token_by_token(*args),
                                       rtol=1e-4, atol=1e-5)
            return
        probe = jnp.asarray(np.random.default_rng(9).normal(size=args[0].shape), jnp.float32)

        def reference_scan(x, dt, a, b, c):
            heads = x.shape[2] // b.shape[2]
            return reference.recurrence(x, dt, a, jnp.repeat(b, heads, axis=2),
                                        jnp.repeat(c, heads, axis=2))

        np.testing.assert_allclose(reference_scan(*args), token_by_token(*args),
                                   rtol=1e-4, atol=1e-5)
        got = jax.grad(lambda *v: jnp.sum(probe * chunked(*v)), argnums=(0, 1, 2, 3, 4))(*args)
        want = jax.grad(lambda *v: jnp.sum(probe * reference_scan(*v)),
                        argnums=(0, 1, 2, 3, 4))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


def test_ssd_chunked_in_bfloat16_stays_near_float32():
    args = scan_inputs(32)
    want = token_by_token(*args)
    got = np.asarray(ssm.ssd_chunked(*args, 8, jnp.bfloat16))
    assert 1e-4 < np.linalg.norm(got - want) / np.linalg.norm(want) < 2e-2


def mamba_layer(chunk, seed=3):
    """One Mamba mixer wide enough for the scan's kernels where `chunk` is
    128 (4 heads of 64 in 2 groups of 128 columns, hidden 48, 300 tokens: two
    chunks and a tail), its parameters away from their constants, and the
    mean of a probe times its output under `jax.checkpoint`, as
    `forward` runs it."""
    cfg = zoo().Config(
        hidden_size=48, mamba_num_heads=4, mamba_head_dim=64, n_groups=2,
        ssm_state_size=128, chunk_size=chunk, compute_dtype="float32",
        num_hidden_layers=1, hybrid_override_pattern="M")
    r = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)
    p = {"mamba_norm": 1 + 0.1 * normal(48),
         "mamba_in_proj": 0.2 * normal(48, cfg.d_inner + cfg.conv_dim + 4),
         "mamba_conv_w": 0.5 * normal(4, cfg.conv_dim), "mamba_conv_b": 0.1 * normal(cfg.conv_dim),
         "mamba_dt_bias": normal(4) - 4.0, "mamba_A_log": 0.3 * normal(4),
         "mamba_D": 1 + 0.1 * normal(4), "mamba_gate_norm": 1 + 0.1 * normal(cfg.d_inner),
         "mamba_out_proj": 0.1 * normal(cfg.d_inner, 48)}
    x, probe = normal(2, 300, 48), normal(2, 300, 48)
    block = jax.checkpoint(lambda p, x: zoo().mamba(p, x, cfg))
    return lambda: jax.jit(jax.value_and_grad(lambda p, x: jnp.mean(probe * block(p, x)),
                                              argnums=(0, 1)))(p, x)


def interpret_kernels(monkeypatch):
    """`pallas_attention.interpret_mode()`'s signal WITHOUT its
    `force_tpu_interpret_mode`: the kernels then run by
    `pallas_call(interpret=True)`, which has no callbacks. A kernel under
    `jax.checkpoint` needs that: remat refuses the TPU interpreter's ordered
    effects."""
    from elasticdl_tpu.ops import pallas_attention

    monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")


def test_mamba_under_checkpoint_is_the_same_on_the_kernel_route(route_log, monkeypatch):
    """`forward` checkpoints every block: loss, parameter gradients and the
    gradient of the residual stream, kernels (interpret mode) against the
    plain body; the log says which route each trace took."""
    # a layer for each route: `jax.checkpoint` keeps a function's trace
    with jax.default_matmul_precision("highest"):
        want_loss, (want_p, want_x) = mamba_layer(chunk=128)()
        assert "takes the plain route" in route_log.text
        assert "takes the kernel route" not in route_log.text
        interpret_kernels(monkeypatch)
        got_loss, (got_p, got_x) = mamba_layer(chunk=128)()
        assert "takes the kernel route" in route_log.text
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    for leaf in want_p:
        np.testing.assert_allclose(got_p[leaf], want_p[leaf], rtol=2e-4,
                                   atol=2e-5 * float(jnp.max(jnp.abs(want_p[leaf]))),
                                   err_msg=leaf)
    np.testing.assert_allclose(got_x, want_x, rtol=2e-4, atol=2e-5 * float(jnp.max(jnp.abs(want_x))))


@pytest.mark.parametrize("route", ["fallback", "kernel"])
def test_keeping_the_flash_residuals_changes_no_value_on_the_cpu(route, monkeypatch):
    """`forward` checkpoints the mixers with `pallas_attention.
    KEEP_RESIDUALS`: where a recomputation repeats the forward pass to the
    bit, as here on the CPU, the loss and every gradient leaf are those of the
    plain `jax.checkpoint` it had before, exactly. On the kernel route (64
    tokens, interpret mode) the attention block's recomputation then holds no
    second forward call; on the XLA fallback (36 tokens) none of the names
    occurs and the policy is inert."""
    from elasticdl_tpu.ops import pallas_attention

    spec, _ = lm.fresh_trainer()
    batch, params = lm.batches(steps=1, seq=64 if route == "kernel" else 36)[0], lm.params()
    if route == "kernel":
        interpret_kernels(monkeypatch)
        monkeypatch.setenv("EDL_FLASH", "1")

    def value_and_grad():       # a new closure each time: a new trace
        return jax.value_and_grad(lambda p: lm.terms(spec, p, batch, BIAS)["loss"])

    forward_calls = lambda: pallas_calls(
        jax.make_jaxpr(value_and_grad())(params).jaxpr, "flash_attention_fwd")
    got_calls, (got_loss, got) = forward_calls(), jax.jit(value_and_grad())(params)
    monkeypatch.setattr(pallas_attention, "KEEP_RESIDUALS", None)   # the plain form
    want_calls, (want_loss, want) = forward_calls(), jax.jit(value_and_grad())(params)
    assert (got_calls, want_calls) == ((1, 2) if route == "kernel" else (0, 0))
    assert float(got_loss) == float(want_loss)
    assert sorted(got) == sorted(LEAVES)
    for leaf in LEAVES:
        assert float(jnp.max(jnp.abs(want[leaf]))) > 0, leaf
        np.testing.assert_array_equal(np.asarray(got[leaf]), np.asarray(want[leaf]), err_msg=leaf)


def test_the_scan_s_route_follows_backend_and_shapes_alone(route_log, monkeypatch):
    """Chunks of 8 are no whole lanes: inside interpret mode too the mixer
    takes the plain body, says so, and traces no kernel."""
    interpret_kernels(monkeypatch)
    text = str(jax.make_jaxpr(mamba_layer(chunk=8))())
    assert "takes the plain route" in route_log.text and "chunks of 8" in route_log.text
    assert "takes the kernel route" not in route_log.text and "pallas_call" not in text
    text = str(jax.make_jaxpr(mamba_layer(chunk=128))())
    assert "takes the kernel route" in route_log.text
    for kernel in ("ssd_chunk_fwd", "ssd_chunk_starts", "ssd_chunk_bwd"):
        assert kernel in text, kernel


# (interpret mode, tokens) -> the convolutions that took the kernels: the tiny
# preset's xBC plane is one lane tile wide (64 + 2·2·16 channels)
CONV_ROUTES = {"plain": (False, 64, 0), "kernel": (True, 64, 1), "ragged_tokens": (True, 36, 0)}
_CONV_LOSS_BY_ROUTE = {}


@pytest.mark.parametrize("route", sorted(CONV_ROUTES))
def test_the_counter_says_which_route_the_convolution_took(monkeypatch, route_log, route):
    """`router_state/kernel_convs`: one a Mamba layer and step where the Pallas
    kernels run (interpret mode here) and the tokens are whole time blocks,
    none on the CPU's plain route and none at 36 tokens; the log says which,
    and the step's loss is the same by both routes."""
    interpret, seq, convs = CONV_ROUTES[route]
    if interpret:
        interpret_kernels(monkeypatch)
    ssm._log_conv_route.cache_clear()
    spec, trainer = lm.fresh_trainer(**lm.short)
    data = lm.batches(steps=1, seq=seq)[0]
    state, logs = trainer.train_step(trainer.init_state(data), data)
    assert int(state.extra_vars["router_state"]["kernel_convs"]) == convs
    taken = "kernel" if convs else "plain"
    assert f"causal convolution ({seq} tokens, 128 channels, 4 taps) takes the {taken} route" \
        in route_log.text
    if seq == 64:
        _CONV_LOSS_BY_ROUTE[route] = float(logs["loss"])
    if len(_CONV_LOSS_BY_ROUTE) == 2:
        assert _CONV_LOSS_BY_ROUTE["kernel"] == pytest.approx(
            _CONV_LOSS_BY_ROUTE["plain"], rel=1e-5)


def test_causal_conv1d_by_hand():
    r = np.random.default_rng(1)
    x, w, b = r.normal(size=(2, 7, 3)), r.normal(size=(4, 3)), r.normal(size=(3,))
    want = np.zeros_like(x)
    for t in range(7):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += w[j] * x[:, t - 3 + j]
        want[:, t] += b
    args = [jnp.asarray(v, jnp.float32) for v in (x, w, b)]
    np.testing.assert_allclose(ssm.causal_conv1d(*args), want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(reference.conv_causal(*args), want, rtol=1e-5, atol=1e-6)
    # causal: a later token changes nothing before it
    x2 = x.copy()
    x2[:, 5] += 1.0
    later = np.asarray(ssm.causal_conv1d(jnp.asarray(x2, jnp.float32), *args[1:]))
    np.testing.assert_allclose(later[:, :5], want[:, :5], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_gated_group_rmsnorm_by_hand(groups):
    r = np.random.default_rng(2)
    y, z, w = r.normal(size=(3, 5, 8)), r.normal(size=(3, 5, 8)), r.uniform(0.5, 1.5, size=(8,))
    gated = y * z / (1.0 + np.exp(-z))
    want = np.zeros_like(gated)
    width = 8 // groups
    for g in range(groups):
        part = gated[..., g * width:(g + 1) * width]
        want[..., g * width:(g + 1) * width] = part / np.sqrt(
            np.mean(part ** 2, axis=-1, keepdims=True) + 1e-5)
    got = ssm.gated_group_rmsnorm(*[jnp.asarray(v, jnp.float32) for v in (y, z, w)],
                                  groups, 1e-5)
    np.testing.assert_allclose(got, want * w, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------ #
# the share of a deployment, tied to the whole (model-configs guide §4)


def test_sixteen_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """One sparse-expert layer at 16 experts top-3: the routed parts that 4
    shares of 4 experts compute (the program's held dispatch, the shared
    expert taken away) plus the shared expert ONCE equal what the reference
    gives for the layer with every expert held."""
    m = zoo()
    r = np.random.default_rng(3)
    c, f, fs, e = 48, 24, 40, 16
    whole = {"moe_norm": r.uniform(0.5, 1.5, (c,)), "moe_router": r.normal(size=(c, e)),
             "shared_up": r.normal(size=(c, fs)) * 0.2, "shared_down": r.normal(size=(fs, c)) * 0.2,
             "w_up": r.normal(size=(e, c, f)) * 0.2, "w_down": r.normal(size=(e, f, c)) * 0.2}
    whole = {k: jnp.asarray(v, jnp.float32) for k, v in whole.items()}
    x = jnp.asarray(r.normal(size=(2, 9, c)), jnp.float32)
    bias = jnp.asarray(r.normal(size=(e,)) * 0.05, jnp.float32)
    hp_whole = reference.hyper(lm.tiny_params(n_routed_experts=16, first_expert=0))
    with jax.default_matmul_precision("highest"):
        want, _, _ = jax.jit(lambda p, x: reference.moe(p, x, bias, None, hp_whole))(whole, x)
        shared = m.relu2_expert(
            m.rmsnorm(x, whole["moe_norm"], 1e-5).reshape(-1, c),
            whole["shared_up"], whole["shared_down"], jnp.float32).reshape(x.shape)
        total = shared
        for share in range(4):
            cfg = m.Config(**{**TINY, "first_expert": 4 * share})
            part = {**whole, "w_up": whole["w_up"][4 * share:4 * share + 4],
                    "w_down": whole["w_down"][4 * share:4 * share + 4]}
            y, _ = jax.jit(lambda p, x: m.moe(p, x, bias, cfg))(part, x)
            total = total + (y - shared)
            # and the reference, given the same share, gives the same part
            hp = reference.hyper(lm.tiny_params(first_expert=4 * share))
            ref_part, _, _ = jax.jit(lambda p, x: reference.moe(p, x, bias, None, hp))(part, x)
            np.testing.assert_allclose(y, ref_part, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
