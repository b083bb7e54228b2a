"""REHEARSAL, no chip: the kernels INSIDE the zoo's checkpointed layers, compiled
for a described v5e at the cells' shapes — every scan kernel of a Mamba mixer
and every flash kernel of a GLM, Nemotron or Mellum2 block has to carry the
scope the benchmark reads it by, and a recomputed layer holds ONE forward
call; the depthwise convolutions of a Mamba mixer and of a KDA layer are the
kernels of `ops/pallas_conv1d.py` under the layer's `conv` scope. The kernels alone are in `tests/test_kernels_aot.py`, whose fixtures
these are; a file of its own so that two xdist workers share the compiles
(the driver's command sets `ALLOW_MULTIPLE_LIBTPU_LOAD=1`).
"""

import re

import jax
import jax.numpy as jnp
import pytest

from tests.test_kernels_aot import CONV, SCAN, no_compile_cache, one_chip  # noqa: F401  (fixtures)

pytestmark = pytest.mark.usefixtures("xla_optimises")


@pytest.fixture(scope="module")
def compiled_texts():
    """model -> the compiled text of its gradient program: one compile a module."""
    return {}


def mamba_program(one_chip, monkeypatch, compiled_texts):
    """The compiled text of a checkpointed Mamba mixer's gradient at the
    Nemotron cell's widths and 8192 tokens, for the described chip."""
    from model_zoo.transformer import nemotron_h

    if "mamba" not in compiled_texts:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # the routes ask
        cfg = nemotron_h.Config(num_hidden_layers=1, hybrid_override_pattern="M")
        assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups, cfg.ssm_state_size,
                cfg.chunk_size) == tuple(SCAN.values())[1:]
        assert (SCAN["tokens"], cfg.conv_dim, True) == CONV["nemotron-3-nano-30b-a3b.resident-8k"]
        shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)
        c, h = cfg.hidden_size, cfg.mamba_num_heads
        p = {"mamba_norm": shape(c), "mamba_in_proj": shape(c, cfg.d_inner + cfg.conv_dim + h),
             "mamba_conv_w": shape(cfg.conv_kernel, cfg.conv_dim), "mamba_conv_b": shape(cfg.conv_dim),
             "mamba_dt_bias": shape(h), "mamba_A_log": shape(h), "mamba_D": shape(h),
             "mamba_gate_norm": shape(cfg.d_inner), "mamba_out_proj": shape(cfg.d_inner, c)}

        def loss(p, x):
            with jax.named_scope("nemotron_h"), jax.named_scope("mamba"):
                y = jax.checkpoint(lambda p, x: nemotron_h.mamba(p, x, cfg))(p, x)
            return jnp.sum(jnp.square(x + y))

        compiled_texts["mamba"] = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            p, shape(1, SCAN["tokens"], c)).compile().as_text()
    return compiled_texts["mamba"]


def scopes_of(text, module):
    """instruction name -> the scope the benchmark's readers put it in."""
    from benchmark import common
    flops = common.load_module("flops", module)
    return common.load_module("drivers", "resident_lm_share").scope_map(
        text, flops.SCOPES, getattr(flops, "RAGGED_DOT_SCOPE", None))


def test_every_scan_kernel_of_a_checkpointed_mamba_mixer_carries_its_scope(
        one_chip, no_compile_cache, monkeypatch, compiled_texts):
    """`nemotron_h.forward` checkpoints the mixer: the gradient program runs
    the forward kernel twice (forward, the block's recomputation), the sweep
    and the backward kernel once — and `benchmark/drivers/resident_lm_share.py::scope_map` finds their time
    by `mamba/ssd` in each one's `op_name`, or `ssm_scan_roofline` divides a
    fixed floor by a scope that lost its kernels."""
    text = mamba_program(one_chip, monkeypatch, compiled_texts)
    calls = re.findall(r"^\s*%?(ssd_[\w.]+) = ", text, re.M)
    assert sorted(re.sub(r"\.\d+$", "", name) for name in calls) == [
        "ssd_chunk_bwd", "ssd_chunk_fwd", "ssd_chunk_fwd", "ssd_chunk_starts"]
    scopes = scopes_of(text, "nemotron_h")
    assert [scopes.get(name) for name in calls] == ["nemotron_h/mamba/ssd"] * 4


def assert_the_convolutions_are_the_kernels(text, module, scope, convolutions):
    """`convolutions` depthwise convolutions of a checkpointed layer: each
    runs the forward kernel twice (the pass, the layer's recomputation) and the
    pull-back once, every call under `scope`, and XLA is left no `convolution`
    of its own there."""
    from benchmark import common
    calls = re.findall(r"^\s*%?(causal_conv1d_[\w.]+) = ", text, re.M)
    assert sorted(re.sub(r"\.\d+$", "", name) for name in calls) == (
        ["causal_conv1d_bwd"] * convolutions + ["causal_conv1d_fwd"] * 2 * convolutions)
    scopes = scopes_of(text, module)
    assert {scopes.get(name) for name in calls} == {scope}
    share = common.load_module("drivers", "resident_lm_share")
    flops = common.load_module("flops", module)
    under = [line for line in text.splitlines() if " convolution(" in line
             and share._lm._OP_NAME.search(line)
             and share.scope_of(share._lm._OP_NAME.search(line).group(1), flops.SCOPES) == scope]
    assert not under, under[:2]


def test_the_convolution_of_a_checkpointed_mamba_mixer_is_the_kernels_under_its_scope(
        one_chip, no_compile_cache, monkeypatch, compiled_texts):
    """The xBC plane's depthwise convolution (8192 x 6144, a bias) compiles as
    `causal_conv1d_fwd` twice and `causal_conv1d_bwd` once, all under
    `nemotron_h/mamba/conv`, where the builder's reading of `ssm_ms` by scope
    finds them."""
    assert_the_convolutions_are_the_kernels(
        mamba_program(one_chip, monkeypatch, compiled_texts), "nemotron_h",
        "nemotron_h/mamba/conv", 1)


# (the zoo's module, a configuration of few layers at the cell's attention
# shapes, tokens, (the scope of an attention block's kernels, their names'
# prefix) in program order)
_CAUSAL, _BANDED = "flash_attention_", "flash_attention_swa_"


RECOMPUTED_ATTENTION = {
    # glm-4.7-flash.resident-8k: the dense layer and the module's own sparse
    # layer, 20 heads of 192 + 64 / 256
    "glm": ("glm4_moe_lite", dict(
        num_hidden_layers=1, first_k_dense_replace=1, num_nextn_predict_layers=1,
        n_routed_experts=8, router_experts=64, vocab_size=512), 8192,
        [("glm4_moe_lite/mla/attn", _CAUSAL), ("glm4_moe_lite/mtp/mla/attn", _CAUSAL)]),
    # nemotron-3-nano-30b-a3b.resident-8k: 32 query heads on 2 key-value heads
    # of 128 (a sparse-expert layer after it: `forward` stacks their statistics)
    "nemotron": ("nemotron_h", dict(
        num_hidden_layers=2, hybrid_override_pattern="*E", n_routed_experts=8,
        router_experts=128, vocab_size=512), 8192, [("nemotron_h/attn", _CAUSAL)]),
    # mellum2-12b-a2.5b.resident-16k: three sliding-window layers of 1024 keys
    # and one full layer, 32 query heads on 4 key-value heads of 128
    "mellum": ("mellum", dict(
        num_hidden_layers=4, num_experts=8, router_experts=64, vocab_size=512), 16384,
        [("mellum/sliding/attn", _BANDED)] * 3 + [("mellum/full/attn", _CAUSAL)]),
    # xing4.0-29b-a4b.resident-4k: the dense layer and a sparse one around four
    # streams, 32 heads with q and k of 128 + 64 and v of 128
    "xing4": ("xing4", dict(
        num_hidden_layers=2, first_k_dense_replace=1, n_routed_experts=8,
        router_experts=64, vocab_size=512), 4096, [("xing4/mla/attn", _CAUSAL)] * 2),
}


def gradient_program(model, one_chip, monkeypatch, compiled_texts):
    """The compiled text of `RECOMPUTED_ATTENTION[model]`'s value and gradient
    at the cell's tokens, for the described chip."""
    import importlib

    if model not in compiled_texts:
        module, config, length, _ = RECOMPUTED_ATTENTION[model]
        zoo = importlib.import_module(f"model_zoo.transformer.{module}")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # the routes ask
        net = zoo.custom_model(**config)
        tokens = jax.ShapeDtypeStruct((1, length), jnp.int32, sharding=one_chip)
        variables = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(net.init, jax.random.PRNGKey(0), tokens))

        def loss(params, state, tokens):
            outputs = net.apply({"params": params, **state}, tokens)
            return sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(outputs))

        params = variables.pop("params")
        compiled_texts[model] = jax.jit(jax.value_and_grad(loss)).lower(
            params, variables, tokens).compile().as_text()
    return compiled_texts[model]


@pytest.mark.parametrize("model", sorted(RECOMPUTED_ATTENTION))
def test_a_recomputed_layer_runs_the_flash_forward_once_under_its_scope(
        model, one_chip, no_compile_cache, monkeypatch, compiled_texts):
    """`forward` checkpoints its layers with `pallas_attention.
    KEEP_RESIDUALS`: the gradient program at the cell's tokens compiles with
    one `flash_attention_fwd` call a block (two under the plain checkpoint)
    and ONE `flash_attention_bwd` — no `bwd_dq`, no `bwd_dkv`: a head's keys
    fit VMEM — `_swa_fwd` and `_swa_bwd` in a windowed block, and `scope_map`
    finds each under the block's own `attn` scope: the benchmark's `mla_ms`,
    `mtp_ms`, `swa_ms` and the name-prefix readers (`mla_attn_ms`,
    `gqa_attn_ms`, `swa_attn_ms`, `global_attn_ms`) read them there."""
    from benchmark import common

    module, _, _, blocks = RECOMPUTED_ATTENTION[model]
    text = gradient_program(model, one_chip, monkeypatch, compiled_texts)
    calls = re.findall(r"^\s*%?(flash_attention_[\w.]+) = ", text, re.M)
    kinds = [re.sub(r"\.\d+$", "", name) for name in calls]
    scope_map = common.load_module("drivers", "resident_lm_share").scope_map
    found = scope_map(text, common.load_module("flops", module).SCOPES)
    assert sorted((kind, found.get(name)) for kind, name in zip(kinds, calls)) == sorted(
        (prefix + part, scope) for scope, prefix in blocks for part in ("fwd", "bwd"))


def held_first_pass_scopes(text, module):
    """Every instruction of a held dispatch's FIRST pass in a compiled text,
    as (its `op_name` from the sparse layer's scope on, the scope the
    benchmark's readers put it in), and the grouped matmuls among them. The
    first pass is what stands under no `cond/branch_` (the backward's
    overflow) and no `while/body` (the forward's)."""
    from benchmark import common

    share = common.load_module("drivers", "resident_lm_share")
    scopes = common.load_module("flops", module).SCOPES
    found, kernels = [], []
    for line in text.splitlines():
        op = share._lm._OP_NAME.search(line)
        if not op or "/moe/" not in op.group(1):
            continue
        tail = op.group(1).rsplit("/moe/", 1)[1]
        if "cond/branch_" in tail or "while/" in tail:
            continue
        tail = share._NOT_A_SCOPE.sub("", tail)     # a layer's `checkpoint/`
        if re.match(r"(jvp\()?(dispatch|experts|combine)\b", tail):
            found.append((tail, share.scope_of(op.group(1), scopes)))
            if re.match(r"\s*(ROOT )?%?grouped_matmul", line):
                kernels.append(found[-1])
    return found, kernels


def assert_the_first_pass_reads_under_the_pass_s_own_scopes(text, module):
    found, kernels = held_first_pass_scopes(text, module)
    assert kernels and all(re.search(r"/moe/experts$", scope) for _, scope in kernels)
    for tail, scope in found:
        # what the readers' normalisation leaves starts with the part's name,
        # so the model's scope, `moe` and the part stand side by side
        part = re.match(r"(?:jvp\()?(\w+)", tail).group(1)
        assert scope is not None and scope.endswith(f"/moe/{part}"), (tail, scope)
    backward = [(tail, scope) for tail, scope in found if "transpose(" in tail]
    # a pass's whole pull-back is evaluated under `named_scope("experts")`
    assert backward and all(tail.startswith("experts/") for tail, _ in backward)
    assert {"experts/transpose(jvp(dispatch))", "experts/transpose(jvp(combine))"} <= {
        "/".join(tail.split("/")[:2]) for tail, _ in backward}
    # the cotangents' float32 sums are the overflow's alone
    assert not any(tail.startswith("experts/add") for tail, _ in found)


@pytest.mark.parametrize("model", sorted(RECOMPUTED_ATTENTION))
def test_a_held_dispatch_s_first_pass_reads_under_dispatch_experts_and_combine(
        model, one_chip, no_compile_cache, monkeypatch, compiled_texts):
    """`ops/moe.py::_held_passes` runs its first pass as straight-line code
    and keeps the float32 sums of several passes' cotangents inside a
    `lax.cond`, whose `cond/branch_1_fun` the readers' normalisation does not
    strip (`resident_lm_share._NOT_A_SCOPE`): every instruction of the first
    pass, forward, recomputed and backward, still reads under
    `<model>/moe/{dispatch,experts,combine}`, the grouped matmuls and the
    whole pull-back under `experts` (`held_moe_ms`, `moe_routed_ms`,
    `held16_moe_ms`, `xing_held_moe_ms` and the `*_gmm_roofline`s sum them
    there), and no `experts/add` is left outside the overflow."""
    assert_the_first_pass_reads_under_the_pass_s_own_scopes(
        gradient_program(model, one_chip, monkeypatch, compiled_texts),
        RECOMPUTED_ATTENTION[model][0])


def test_trinity_s_layers_keep_the_full_layer_s_residuals_and_not_the_sliding_one_s(
        one_chip, no_compile_cache, monkeypatch):
    """trinity-mini.resident-16k (`afmoe.KEEP_RESIDUALS_KINDS`: the full layer
    alone — all five layers' do not fit beside 705M parameters' state):
    published layers 2 and 3, a sliding and a full one at 16 384 tokens, window
    2048, 32/4 heads of 128 and 16 of 128 experts held. The sliding layer's
    recomputation runs its banded forward kernel again, the full layer's does
    not, every kernel under its kind's `attn` scope — and no `rope` scope
    under `afmoe/full`."""
    from benchmark import common
    from model_zoo.transformer import afmoe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # the routes ask
    assert afmoe.KEEP_RESIDUALS_KINDS == ("full",)
    net = afmoe.custom_model(num_hidden_layers=2, kept_layers="2,3", num_experts=16,
                             router_experts=128, vocab_size=512)
    tokens = jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one_chip)
    variables = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(net.init, jax.random.PRNGKey(0), tokens))

    def loss(params, state, tokens):
        return jnp.sum(jnp.square(net.apply({"params": params, **state}, tokens)["logits"]))

    params = variables.pop("params")
    text = jax.jit(jax.value_and_grad(loss)).lower(params, variables, tokens).compile().as_text()
    calls = re.findall(r"^\s*%?(flash_attention_[\w.]+) = ", text, re.M)
    kinds = [re.sub(r"\.\d+$", "", name) for name in calls]
    scope_map = common.load_module("drivers", "resident_lm_share").scope_map
    found = scope_map(text, common.load_module("flops", "afmoe").SCOPES)
    assert sorted((kind, found.get(name)) for kind, name in zip(kinds, calls)) == sorted(
        [("flash_attention_swa_fwd", "afmoe/sliding/attn")] * 2
        + [("flash_attention_swa_bwd", "afmoe/sliding/attn"),
           ("flash_attention_fwd", "afmoe/full/attn"),
           ("flash_attention_bwd", "afmoe/full/attn")])
    scopes = set(found.values())
    assert "afmoe/sliding/rope" in scopes and "afmoe/full/qk_norm" in scopes
    assert {"afmoe/sliding/gate", "afmoe/full/gate", "afmoe/moe/experts"} <= scopes
    assert not any(s.startswith("afmoe/full/rope") for s in scopes)
    assert not re.search(r'op_name="[^"]*full/rope', text)
    assert_the_first_pass_reads_under_the_pass_s_own_scopes(text, "afmoe")


def test_ouro_s_loop_keeps_every_application_s_residuals(
        one_chip, no_compile_cache, monkeypatch):
    """ouro-2.6b.resident-4k at its published widths, two layers run twice
    over shared weights at 4096 tokens, through the zoo's own loss (the exits'
    logits one at a time): every application holds ONE forward kernel, its
    recomputation none — 2 x 2 forward and 2 x 2 backward kernels, every one
    under `ouro/pass/attn` — and every scope the benchmark reads the loop by
    is in the compiled text."""
    from benchmark import common
    from model_zoo.transformer import ouro

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # the routes ask
    net = ouro.custom_model(num_hidden_layers=2, total_ut_steps=2, vocab_size=512)
    tokens = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)
    variables = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(net.init, jax.random.PRNGKey(0), tokens))

    def loss(params, state, tokens):
        outputs = net.apply({"params": params, **state}, tokens)
        return jnp.sum(ouro.loss(tokens, outputs)["loss"])

    params = variables.pop("params")
    text = jax.jit(jax.value_and_grad(loss)).lower(params, variables, tokens).compile().as_text()
    calls = re.findall(r"^\s*%?(flash_attention_[\w.]+) = ", text, re.M)
    kinds = sorted(re.sub(r"\.\d+$", "", name) for name in calls)
    assert kinds == ["flash_attention_bwd"] * 4 + ["flash_attention_fwd"] * 4
    scope_map = common.load_module("drivers", "resident_lm_share").scope_map
    found = scope_map(text, common.load_module("flops", "ouro").SCOPES)
    assert {found.get(name) for name in calls} == {"ouro/pass/attn"}
    assert set(found.values()) >= {
        "ouro/embed", "ouro/pass/attn", "ouro/pass/mlp", "ouro/pass/norm",
        "ouro/pass/final_norm", "ouro/exit", "ouro/exit_loss"}


def kimi_program(one_chip, monkeypatch, compiled_texts):
    """The compiled text of kimi-linear-48b-a3b.resident-16k's value and
    gradient at its published widths and 16 384 tokens, a KDA layer over the
    dense feed-forward and a latent layer over a sparse one, through the zoo's
    own loss."""
    from model_zoo.transformer import kimi_linear

    if "kimi" not in compiled_texts:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # the routes ask
        net = kimi_linear.custom_model(
            num_hidden_layers=2, kda_layers="1", full_attn_layers="2", num_experts=8,
            router_experts=256, vocab_size=512)
        assert (16384, net.cfg.linear_num_heads * net.cfg.linear_head_dim, False) == CONV[
            "kimi-linear-48b-a3b.resident-16k"]
        tokens = jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one_chip)
        variables = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(net.init, jax.random.PRNGKey(0), tokens))

        def loss(params, state, tokens):
            outputs = net.apply({"params": params, **state}, tokens)
            return jnp.sum(kimi_linear.loss(tokens, outputs)["loss"])

        params = variables.pop("params")
        compiled_texts["kimi"] = jax.jit(jax.value_and_grad(loss)).lower(
            params, variables, tokens).compile().as_text()
    return compiled_texts["kimi"]


def test_kimi_linear_s_two_kinds_of_layer_compile_under_their_scopes(
        one_chip, no_compile_cache, monkeypatch, compiled_texts):
    """kimi-linear-48b-a3b.resident-16k at its published widths and 16 384
    tokens, a KDA layer over the dense feed-forward and a latent layer over a
    sparse one, through the zoo's own loss: the chunked delta rule (chunks of
    64, blocks of 4) compiles for the chip as ONE `delta_rule_fwd` — the
    recomputed layer keeps its two named arrays — and ONE `delta_rule_bwd`,
    both under `kimi_linear/kda/delta_rule` (or `kda_delta_rule_roofline`
    divides a fixed floor by a scope that lost its kernels), beside ONE flash
    forward and ONE backward at heads of 192 | 128, both under
    `kimi_linear/mla/attn`; every scope the benchmark reads the mixer by is in
    the compiled text, forward and backward."""
    text = kimi_program(one_chip, monkeypatch, compiled_texts)
    calls = re.findall(r"^\s*%?(flash_attention_[\w.]+) = ", text, re.M)
    kinds = sorted(re.sub(r"\.\d+$", "", name) for name in calls)
    assert kinds == ["flash_attention_bwd", "flash_attention_fwd"]
    found = scopes_of(text, "kimi_linear")
    assert {found.get(name) for name in calls} == {"kimi_linear/mla/attn"}
    assert set(found.values()) >= {
        f"kimi_linear/{part}" for part in (
            "embed", "kda/proj", "kda/conv", "kda/gates", "kda/qk_norm", "kda/delta_rule",
            "kda/out_gate", "kda/out", "mla/q_proj", "mla/kv_lora", "mla/attn", "mla/out",
            "dense_mlp", "moe/router", "moe/experts", "moe/shared", "head_loss")}
    # the recurrence's two kernels carry the scope, and no loop is left of it
    rule = re.findall(r"^\s*%?(delta_rule_[\w.]+) = ", text, re.M)
    assert sorted(re.sub(r"\.\d+$", "", name) for name in rule) == [
        "delta_rule_bwd", "delta_rule_fwd"]
    assert {found.get(name) for name in rule} == {"kimi_linear/kda/delta_rule"}
    assert not re.search(r"kda/delta_rule/[^\"]*while", text)
    # Γ's in-chunk sum is a product at the highest precision under the same
    # scope — forward, recomputed, pulled back — and no windowed reduction
    # between relayouts of the (16 384, 4096) plane (PR 65)
    under_rule = [line for line in text.splitlines() if "kda/delta_rule/" in line]
    products = [line for line in under_rule if " convolution(" in line]
    assert len(products) == 3 and all(
        "operand_precision={highest,highest}" in line for line in products)
    assert not [line for line in under_rule
                if " reduce-window(" in line or re.search(r"4096\]\S* copy\(", line)]


def test_the_convolutions_of_a_checkpointed_kda_layer_are_the_kernels_under_their_scope(
        one_chip, no_compile_cache, monkeypatch, compiled_texts):
    """q's, k's and v's depthwise convolutions (16 384 x 4096, no bias)
    compile as `causal_conv1d_fwd` twice each and `causal_conv1d_bwd` once
    each, all under `kimi_linear/kda/conv`, where `kda_conv_gates_ms` reads
    them."""
    assert_the_convolutions_are_the_kernels(
        kimi_program(one_chip, monkeypatch, compiled_texts), "kimi_linear",
        "kimi_linear/kda/conv", 3)


# ------------------------------------------------------------------ #
# phi-4-mini-flash.resident-8k: the selective scan, the flash kernels at q/k
# heads of 64 against v heads of 128, full and under a window of 512


def test_the_selective_scan_s_kernels_compile_for_a_v5e(one_chip, no_compile_cache):
    """The cell's scan alone: 8192 tokens, 5120 channels of 16 state indices,
    forward and backward — `selective_scan_fwd` and `selective_scan_bwd`, two
    Mosaic calls, at the blocks the route gives there (128 tokens x 1024
    channels: the backward's block of states is 8.4 MB of VMEM)."""
    from elasticdl_tpu.ops import pallas_selective_scan

    t, e, n = 8192, 5120, 16
    plan = pallas_selective_scan.blocks(t, e, n)
    assert plan == (128, 1024)
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    def forward_and_backward(x, dt, a, b, c, d, dy):
        y, vjp = jax.vjp(lambda *o: pallas_selective_scan.selective_scan_kernels(*o, plan),
                         x, dt, a, b, c, d)
        return y, vjp(dy)

    text = jax.jit(forward_and_backward).lower(
        shape(1, t, e), shape(1, t, e), shape(e, n), shape(1, t, n), shape(1, t, n), shape(e),
        shape(1, t, e)).compile().as_text()
    calls = re.findall(r"^\s*%\w*?(selective_scan_[a-z]*?)_*\.\d+ = .*tpu_custom_call", text, re.M)
    assert sorted(calls) == ["selective_scan_bwd", "selective_scan_fwd"]
    assert text.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("window", [None, 512], ids=["full", "window_512"])
@pytest.mark.parametrize("route", ["resident", "split"])
def test_flash_kernels_at_heads_of_64_and_128_compile_for_a_v5e(
        route, window, one_chip, no_compile_cache, monkeypatch):
    """Differential attention's one call: 40 query heads over 20 key heads of
    64 (half a lane tile, and the NARROWER width: the two-width plan was opened
    with q/k the wider) against 20 value heads of 128, 8192 keys, causal and
    under a window of 512 (half the block of 1024: a q block's band is the
    diagonal block and the one before it). One forward and one backward kernel
    with the head resident; on a chip of 32 MiB the streaming forward and the
    split route's two kernels."""
    from elasticdl_tpu.ops import pallas_attention

    pallas_attention._make_flash.cache_clear()
    if route == "split":
        monkeypatch.setattr(pallas_attention, "_vmem_bytes", lambda: 32 << 20)
    shape = lambda h, d: jax.ShapeDtypeStruct((1, 8192, h, d), jnp.bfloat16, sharding=one_chip)
    q, k, v, do = shape(40, 64), shape(20, 64), shape(20, 128), shape(40, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_attention.can_flash(q.shape, k.shape, dtype=jnp.bfloat16, window=window)
    assert pallas_attention._plan_blocks(q.shape, k.shape, None, None,
                                         dtype=jnp.bfloat16) == (1024, 1024)
    assert pallas_attention.bwd_route(8192, 64, jnp.bfloat16, 1024, 1024,
                                      v_dim=128).route == route

    def forward_and_backward(q, k, v, do):
        out, vjp = jax.vjp(lambda q, k, v: pallas_attention.flash_attention(
            q, k, v, causal=True, window=window, interpret=False), q, k, v)
        return out, vjp(do)

    text = jax.jit(forward_and_backward).lower(q, k, v, do).compile().as_text()
    calls = re.findall(r"^\s*%\w*?(flash_attention_[a-z_]*?)_*\.\d+ = .*tpu_custom_call", text, re.M)
    prefix = "flash_attention_swa_" if window else "flash_attention_"
    assert sorted(calls) == [prefix + part for part in (
        ["bwd", "fwd"] if route == "resident" else ["bwd_dkv", "bwd_dq", "fwd"])]
    pallas_attention._make_flash.cache_clear()


def phi4flash_program(one_chip, monkeypatch, compiled_texts):
    """The compiled text of phi-4-mini-flash.resident-8k's value and gradient
    at its published widths and 8192 tokens — the six kept layers, one of each
    kind and the two Mamba layers — through the zoo's own loss."""
    from model_zoo.transformer import phi4flash

    if "phi4flash" not in compiled_texts:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # the routes ask
        net = phi4flash.custom_model(num_hidden_layers=6, kept_layers="0,1,16,17,18,19",
                                     vocab_size=512)
        tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
        variables = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(net.init, jax.random.PRNGKey(0), tokens))

        def loss(params, state, tokens):
            return jnp.sum(phi4flash.loss(tokens, net.apply({"params": params, **state}, tokens)))

        params = variables.pop("params")
        compiled_texts["phi4flash"] = jax.jit(jax.value_and_grad(loss)).lower(
            params, variables, tokens).compile().as_text()
    return compiled_texts["phi4flash"]


def test_phi4flash_s_kinds_of_layer_compile_under_their_scopes(
        one_chip, no_compile_cache, monkeypatch, compiled_texts):
    """Every Mamba layer's scan is `selective_scan_fwd` twice (the layer is
    recomputed) and `selective_scan_bwd` once, all under `phi4flash/mamba/scan`
    where `sambay_scan_ms` reads them, and no loop is left of the recurrence;
    the three attention layers are ONE flash forward each — the recomputed
    layer keeps its residuals — and ONE backward, the sliding layer's the
    windowed kernels, all under `phi4flash/diff_attn/flash`; every scope the
    benchmark reads the model by is in the compiled text."""
    text = phi4flash_program(one_chip, monkeypatch, compiled_texts)
    found = scopes_of(text, "phi4flash")
    names = lambda prefix: re.findall(rf"^\s*%?({prefix}[\w.]+) = ", text, re.M)
    kinds = lambda calls: sorted(re.sub(r"\.\d+$", "", name) for name in calls)
    scan = names("selective_scan_")
    assert kinds(scan) == ["selective_scan_bwd"] * 2 + ["selective_scan_fwd"] * 4
    assert {found.get(name) for name in scan} == {"phi4flash/mamba/scan"}
    assert not re.search(r"mamba/scan/[^\"]*while", text)
    flash = names("flash_attention_")
    assert kinds(flash) == ["flash_attention_bwd"] * 2 + ["flash_attention_fwd"] * 2 + [
        "flash_attention_swa_bwd", "flash_attention_swa_fwd"]
    assert {found.get(name) for name in flash} == {"phi4flash/diff_attn/flash"}
    assert set(found.values()) >= {
        f"phi4flash/{part}" for part in (
            "embed", "mamba/proj", "mamba/conv", "mamba/dt", "mamba/scan", "mamba/gate_out",
            "gmu", "diff_attn/proj", "diff_attn/flash", "diff_attn/combine", "mlp", "norm",
            "head_loss")}


def test_the_convolutions_of_phi4flash_s_mamba_layers_are_the_kernels_under_their_scope(
        one_chip, no_compile_cache, monkeypatch, compiled_texts):
    """The two Mamba layers' depthwise convolutions (8192 x 5120, K = 4, a
    bias) compile as `causal_conv1d_fwd` twice each and `causal_conv1d_bwd`
    once each, all under `phi4flash/mamba/conv`."""
    assert_the_convolutions_are_the_kernels(
        phi4flash_program(one_chip, monkeypatch, compiled_texts), "phi4flash",
        "phi4flash/mamba/conv", 2)


def test_lfm2_s_attention_layer_holds_one_flash_forward_and_its_convolution_layer_the_kernels(
        one_chip, no_compile_cache, monkeypatch):
    """lfm2-8b-a1b.resident-32k at its published widths — published layers 2
    and 3, a sparse attention layer and a sparse convolution layer, 32 768
    tokens, 32 query heads on 8 key-value heads, q, k, v and the output all at
    a head of 64, 8 of 32 experts held — through the zoo's own loss (the head
    in row blocks). The attention layer keeps its flash residuals: ONE
    `flash_attention_fwd`; its backward is ONE `flash_attention_bwd` and no
    `bwd_dq` / `bwd_dkv` (at 64 lanes padded to 128 a head's k, v, dk and dv
    fit the resident kernel's VMEM at 32 768 keys in ONE buffer each, not in
    the pipeline's two, and the plan says so; at 16 384 keys they fit in
    two), all under `lfm2/attn/attn`. The convolution layer's K = 3 convolution is
    `causal_conv1d_fwd` twice and `causal_conv1d_bwd` once under
    `lfm2/conv/conv`, between two XLA products under `gate_in` and
    `gate_out`."""
    from benchmark import common
    from elasticdl_tpu.ops import pallas_attention
    from model_zoo.transformer import lfm2_moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # the routes ask
    blocks = (pallas_attention.DEFAULT_BLOCK_Q, pallas_attention.DEFAULT_BLOCK_K)
    assert pallas_attention.fwd_route(32768, 64, jnp.bfloat16, *blocks).route == "resident"
    backward = {t: pallas_attention.bwd_route(t, 64, jnp.bfloat16, *blocks) for t in (32768, 16384)}
    assert {t: (p.route, p.buffers) for t, p in backward.items()} == {
        32768: ("resident", 1), 16384: ("resident", 2)}
    net = lfm2_moe.custom_model(num_hidden_layers=2, kept_layers="2,3", num_experts=8,
                                router_experts=32, vocab_size=512)
    assert [net.cfg.kind(l) for l in net.cfg.layers] == ["full_attention", "conv"]
    assert (net.cfg.num_attention_heads, net.cfg.num_key_value_heads, net.cfg.head_dim) == (
        32, 8, 64)
    tokens = jax.ShapeDtypeStruct((1, 32768), jnp.int32, sharding=one_chip)
    variables = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(net.init, jax.random.PRNGKey(0), tokens))

    def loss(params, state, tokens):
        outputs = net.apply({"params": params, **state}, tokens)
        return jnp.sum(lfm2_moe.loss(tokens, outputs)["loss"])

    params = variables.pop("params")
    text = jax.jit(jax.value_and_grad(loss)).lower(params, variables, tokens).compile().as_text()
    calls = re.findall(r"^\s*%?(flash_attention_[\w.]+) = ", text, re.M)
    kinds = sorted(re.sub(r"\.\d+$", "", name) for name in calls)
    assert kinds == ["flash_attention_bwd", "flash_attention_fwd"]
    found = scopes_of(text, "lfm2_moe")
    assert {found.get(name) for name in calls} == {"lfm2/attn/attn"}
    assert_the_convolutions_are_the_kernels(text, "lfm2_moe", "lfm2/conv/conv", 1)
    assert set(found.values()) >= {
        "lfm2/embed", "lfm2/head_loss", "lfm2/moe/router", "lfm2/moe/experts"} | {
        f"lfm2/conv/{part}" for part in ("in_proj", "gate_in", "conv", "gate_out", "out_proj")} | {
        f"lfm2/attn/{part}" for part in ("qkv", "qk_norm", "rope", "attn", "out")}
    assert_the_first_pass_reads_under_the_pass_s_own_scopes(text, "lfm2_moe")


def test_qwen3_next_s_two_kinds_of_layer_compile_under_their_scopes_and_keep_no_wide_decay(
        one_chip, no_compile_cache, monkeypatch):
    """qwen3-next-80b-a3b.resident-16k at its published widths — published
    layers 2 and 3, a Gated DeltaNet layer and the gated attention layer,
    16 384 tokens, 16 key heads read by 32 value heads of 128, 16 query heads
    on 2 key-value heads of 256, 32 of 512 experts held — through the zoo's own
    loss (the head in row blocks). The SCALAR delta rule compiles as ONE
    `delta_rule_scalar_fwd` (the recomputed layer keeps its two named arrays)
    and ONE `delta_rule_scalar_bwd`, both under `qwen3_next/gdn/delta_rule`,
    and no channel-wise kernel; the ONE convolution over q | k | v's 8192
    channels is the kernels under `qwen3_next/gdn/conv`; ONE flash forward and
    ONE backward at heads of 256 under `qwen3_next/attn/flash`. In the compiled
    text there is NO (16 384, 32, 128) float32 plane that g, Γ or their
    exponentials could be — every array of that shape is the gated norm's (o, z
    and silu(z) a value head) — and no 32-head copy of q or k: the kernels' q,
    k, dq and dk operands are (1, 16 384, 2048)."""
    from model_zoo.transformer import qwen3_next

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # the routes ask
    net = qwen3_next.custom_model(num_hidden_layers=2, kept_layers="2,3", num_experts=32,
                                  router_experts=512, vocab_size=512)
    assert [net.cfg.kind(l) for l in net.cfg.layers] == ["linear_attention", "full_attention"]
    assert (net.cfg.key_width, net.cfg.value_width, net.cfg.value_group) == (2048, 4096, 2)
    tokens = jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one_chip)
    variables = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(net.init, jax.random.PRNGKey(0), tokens))

    def loss(params, state, tokens):
        outputs = net.apply({"params": params, **state}, tokens)
        return jnp.sum(qwen3_next.loss(tokens, outputs)["loss"])

    params = variables.pop("params")
    text = jax.jit(jax.value_and_grad(loss)).lower(params, variables, tokens).compile().as_text()
    found = scopes_of(text, "qwen3_next")
    rule = re.findall(r"^\s*%?(delta_rule_[\w.]+) = ", text, re.M)
    assert sorted(re.sub(r"\.\d+$", "", name) for name in rule) == [
        "delta_rule_scalar_bwd", "delta_rule_scalar_fwd"]
    assert {found.get(name) for name in rule} == {"qwen3_next/gdn/delta_rule"}
    calls = re.findall(r"^\s*%?(flash_attention_[\w.]+) = ", text, re.M)
    assert sorted(re.sub(r"\.\d+$", "", name) for name in calls) == [
        "flash_attention_bwd", "flash_attention_fwd"]
    assert {found.get(name) for name in calls} == {"qwen3_next/attn/flash"}
    assert_the_convolutions_are_the_kernels(text, "qwen3_next", "qwen3_next/gdn/conv", 1)
    assert set(found.values()) >= {
        "qwen3_next/embed", "qwen3_next/head_loss"} | {
        f"qwen3_next/gdn/{part}" for part in (
            "proj", "conv", "qk_norm", "gates", "delta_rule", "gate_norm", "out")} | {
        f"qwen3_next/attn/{part}" for part in (
            "proj", "qk_norm", "rope", "flash", "gate", "out")} | {
        f"qwen3_next/moe/{part}" for part in ("router", "experts", "shared")}
    assert_the_first_pass_reads_under_the_pass_s_own_scopes(text, "qwen3_next")
    # the scalar kernels take q and k at their own 16 heads, Γ and β as rows
    for line in (l for l in text.splitlines() if re.match(r"\s*%?delta_rule_scalar_bwd", l)):
        operands = line[line.index("custom-call("):]
        assert operands.count("f32[1,16384,2048]") == 2             # q, k
        assert "f32[1,32,64,4,64]" in operands                      # Γ, β: (B, H_v, blocks, n, L)
        assert "f32[1,16384,32,128]" not in operands
    # what IS of (T, 32, 128) float32 is the gated norm's: o, z and silu(z) a
    # value head — nothing under the decay's, the recurrence's, the L2 norms' or
    # the convolution's scope, where a widened g, Γ, exp Γ or a repeated q or k
    # would be
    wide = [re.search(r'op_name="([^"]*)"', line) for line in text.splitlines()
            if re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = f32\[(?:1,)?16384,32,128\]", line)]
    named = [m.group(1) for m in wide if m]
    assert named and all("gdn/gate_norm" in name for name in named), sorted(set(named))[:5]
