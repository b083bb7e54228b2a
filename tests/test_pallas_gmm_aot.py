"""REHEARSAL, no chip: the grouped matmul's three forms compile for a
described v5e at the widths the benchmark's three language-model cells run
(`ops/pallas_gmm.py`; the on-chip-measurement guide, section 2), and the
flash-attention kernels at the four language-model cells' shapes, the ONE
backward kernel with a key-value head's keys resident in VMEM, and at a head
that does not fit, the two that stream it (`ops/pallas_attention.py`: the
block plan follows the head size, `bwd_route` the head's bytes), and the
state-space scan's three kernels at the Nemotron cell's shapes
(`ops/pallas_ssd.py`), alone and inside a checkpointed Mamba mixer, where
every one of them has to carry the scope the benchmark reads it by — as the
flash kernels have to inside the checkpointed layers of GLM and Nemotron,
which keep the forward kernel's results and so hold ONE forward call a block; and
the pull-back of the Keye cell's index scores (`ops/sparse_attention.py::
index_score_bwd`) at that cell's shape; and xDeepFM's CIN kernels
(`ops/pallas_cin.py`) at the Criteo cell's. What
interpret mode cannot see — a block Mosaic refuses, more VMEM than a kernel
may use — fails here and costs no chip time. Nothing runs: no time, no result.

The topology is described inside a fixture, never at import: only the xdist
worker that is given this file loads libtpu."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from elasticdl_tpu.ops import pallas_gmm, pallas_ssd

# (rows of a pass or of all pairs, K, N, groups): the held experts of
# nemotron-3-nano-30b-a3b.resident-8k, up and down; olmoe-1b-7b.resident-4k's
SHAPES = {
    "nemotron_up": (6144, 2688, 1856, 8),
    "nemotron_down": (6144, 1856, 2688, 8),
    "olmoe_up": (65536, 2048, 1024, 64),
    "olmoe_down": (65536, 1024, 2048, 64),
    # glm-4.7-flash.resident-8k: a pass of 8192 rows (twice the 8 held
    # experts' even share of 32 768 pairs)
    "glm_up": (8192, 2048, 1536, 8),
    "glm_down": (8192, 1536, 2048, 8),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """An AOT compile for a described chip is written to the persistent cache
    and cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_forward_and_backward_compile_for_a_v5e(name, one_chip, no_compile_cache):
    m, k, n, g = SHAPES[name]
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    def forward_and_backward(lhs, rhs, sizes, dy):
        out, vjp = jax.vjp(lambda a, b: pallas_gmm.grouped_matmul(a, b, sizes), lhs, rhs)
        return out, vjp(dy)

    text = jax.jit(forward_and_backward).lower(
        shape((m, k), jnp.bfloat16), shape((g, k, n), jnp.bfloat16),
        shape((g,), jnp.int32), shape((m, n), jnp.bfloat16)).compile().as_text()
    assert text.count("%grouped_matmul_t") >= 1
    assert text.count('custom_call_target="tpu_custom_call"') == 3


# (batch, tokens, query heads, key-value heads, head, window) -> the plan's
# blocks and the backward's route: the attention of BENCHMARK.json's four
# language-model cells, then a head that does not fit VMEM whole
FLASH = {
    "olmoe-1b-7b.resident-4k": ((2, 4096, 16, 16, 128, None), (1024, 1024), "resident"),
    "nemotron-3-nano-30b-a3b.resident-8k": ((1, 8192, 32, 2, 128, None), (1024, 1024), "resident"),
    # the plan halves the key block at head 256, for every kernel alike: at
    # (1024, 1024) the dq kernel asked for 16.9 MB of Mosaic's default 16 (PR 32)
    "glm-4.7-flash.resident-8k": ((1, 8192, 20, 20, 256, None), (1024, 512), "resident"),
    "mellum2-12b-a2.5b.resident-16k/full": ((1, 16384, 32, 4, 128, None), (1024, 1024), "resident"),
    "mellum2-12b-a2.5b.resident-16k/sliding": ((1, 16384, 32, 4, 128, 1024), (1024, 1024), "resident"),
    "32k_keys": ((1, 32768, 8, 2, 128, None), (1024, 1024), "split"),
    "32k_keys/sliding": ((1, 32768, 8, 2, 128, 1024), (1024, 1024), "split"),
}


@pytest.mark.parametrize("name", sorted(FLASH))
def test_flash_kernels_compile_for_a_v5e(name, one_chip, no_compile_cache):
    """Forward and backward in bfloat16 at the blocks the plan gives: ONE
    backward kernel where a key-value head's k, v, dk and dv fit the VMEM
    `bwd_route` allows it (`vmem_limit_bytes`: Mosaic's default 16 MB hold
    none of these heads), the dq and the dkv kernel where they do not."""
    from elasticdl_tpu.ops import pallas_attention

    (b, t, h, hkv, d, window), blocks, route = FLASH[name]
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, t, hkv, d), jnp.bfloat16, sharding=one_chip)
    assert pallas_attention._plan_blocks(q.shape, k.shape, None, None, dtype=jnp.bfloat16) == blocks
    assert pallas_attention.bwd_route(t, d, jnp.bfloat16, *blocks).route == route

    def forward_and_backward(q, k, v, do):
        out, vjp = jax.vjp(lambda q, k, v: pallas_attention.flash_attention(
            q, k, v, causal=True, window=window, interpret=False), q, k, v)
        return out, vjp(do)

    text = jax.jit(forward_and_backward).lower(q, k, k, q).compile().as_text()
    # outside a named scope the instruction is `%jvp_flash_attention_fwd_.1`,
    # `%transpose_jvp_flash_attention_bwd__.1`
    calls = re.findall(r"^\s*%\w*?(flash_attention_[a-z_]*?)_*\.\d+ = .*tpu_custom_call", text, re.M)
    prefix = "flash_attention_swa_" if window else "flash_attention_"
    assert sorted(calls) == [prefix + part for part in (
        ["bwd", "fwd"] if route == "resident" else ["bwd_dkv", "bwd_dq", "fwd"])]
    assert text.count('custom_call_target="tpu_custom_call"') == len(calls)


@pytest.mark.parametrize("route", ["resident", "split"])
def test_masked_flash_kernels_compile_for_a_v5e(route, one_chip, no_compile_cache, monkeypatch):
    """keye-vl-2.0-30b-a3b.resident-16k's attention: 32/4 heads of 128, 16 384
    keys and an int8 `keep` plane as a fifth operand, with the logsumexp
    returned. The plan gives q blocks of 512 so that the resident backward
    holds the q block's (512, 16 384) strip of the mask beside the head's k,
    v, dk and dv; the split route's two kernels take the mask tile by tile."""
    from elasticdl_tpu.ops import pallas_attention

    b, t, h, hkv, d = 1, 16384, 32, 4, 128
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, t, hkv, d), jnp.bfloat16, sharding=one_chip)
    keep = jax.ShapeDtypeStruct((b, t, t), jnp.int8, sharding=one_chip)
    assert pallas_attention._plan_blocks(q.shape, k.shape, None, None, dtype=jnp.bfloat16,
                                         keep=True) == (512, 1024)
    if route == "split":
        pallas_attention._make_flash.cache_clear()
        monkeypatch.setattr(pallas_attention, "_vmem_bytes", lambda: 1 << 10)
    assert pallas_attention.bwd_route(t, d, jnp.bfloat16, 512, 1024, keep=True).route == route

    def forward_and_backward(q, k, v, keep, do):
        (out, lse), vjp = jax.vjp(lambda q, k, v: pallas_attention.flash_attention_lse(
            q, k, v, keep=keep, interpret=False), q, k, v)
        return out, lse, vjp((do, jnp.zeros_like(lse)))

    text = jax.jit(forward_and_backward).lower(q, k, k, keep, q).compile().as_text()
    calls = re.findall(r"^\s*%\w*?(flash_attention_[a-z_]*?)_*\.\d+ = .*tpu_custom_call", text, re.M)
    assert sorted(calls) == ["flash_attention_sel_" + part for part in (
        ["bwd", "fwd"] if route == "resident" else ["bwd_dkv", "bwd_dq", "fwd"])]
    pallas_attention._make_flash.cache_clear()


def test_the_index_loss_s_pull_back_compiles_for_a_v5e(one_chip, no_compile_cache, monkeypatch):
    """keye-vl-2.0-30b-a3b.resident-16k's indexer: a block of 128 query rows,
    16 heads of 64 against 16 384 keys in bfloat16, at the key tile the route
    gives there (`sparse_attention.pullback_keys` asks which backend it is on,
    so the test answers for the described chip)."""
    from elasticdl_tpu.ops import sparse_attention

    b, rows, t, heads, d = 1, 128, 16384, 16, 64
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    block_k = sparse_attention.pullback_keys(rows, t, d, jnp.bfloat16, jnp.bfloat16)
    assert block_k == sparse_attention.PULLBACK_KEYS
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    text = jax.jit(lambda *operands: sparse_attention.index_score_bwd(
        *operands, block_k=block_k, interpret=False)).lower(
        shape((b, rows, heads, d), jnp.bfloat16), shape((b, t, d), jnp.bfloat16),
        shape((b, rows, heads), jnp.float32), shape((b, rows, t), jnp.float32),
        shape((), jnp.int32)).compile().as_text()
    # under its scope: `%scores_index_score_bwd.1`-like, one call
    assert len(re.findall(r"^\s*%\w*index_score_bwd\w*\.\d+ = .*tpu_custom_call", text, re.M)) == 1
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_the_cin_kernels_compile_for_a_v5e(one_chip, no_compile_cache):
    """xdeepfm-criteo.resident's CIN: 200-200-200 feature maps over 26 fields
    x 10 coordinates at batch 55 296 in bfloat16, forward and backward, at
    the tiles the rule gives there: three calls of each kernel, in two
    shapes (the first layer's 26 maps in, the others' 200), and no array of
    the (B, H, F, D) plane's size outside them."""
    from elasticdl_tpu.ops import pallas_cin

    b, f, d, sizes = 55296, 26, 10, (200, 200, 200)
    assert pallas_cin.network_tiles((b, f, d), sizes, jnp.bfloat16) == 512
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)
    ws = [shape((o, h * f), jnp.float32) for h, o in zip((f,) + sizes, sizes)]

    def forward_and_backward(ws, x0, ct):
        out, vjp = jax.vjp(pallas_cin.cin, ws, x0)
        return out, vjp(ct)

    exe = jax.jit(forward_and_backward).lower(
        ws, shape((b, f, d), jnp.bfloat16), shape((b, sum(sizes)), jnp.bfloat16)).compile()
    text = exe.as_text()
    for kernel in ("cin_fwd", "cin_bwd"):
        assert len(re.findall(rf"^\s*%{kernel}\.\d+ = .*tpu_custom_call", text, re.M)) == 3
    assert text.count('custom_call_target="tpu_custom_call"') == 6
    # the plane is 5.75 GB in bfloat16; the program's temporaries are the
    # layers' (208, 552 960) activations and gradients
    assert exe.memory_analysis().temp_size_in_bytes < 2 << 30


# nemotron-3-nano-30b-a3b.resident-8k's scan: one sequence of 8192 tokens, 64
# heads of 64 in 8 groups of 128 state columns, chunks of 128
SCAN = dict(tokens=8192, heads=64, head_dim=64, groups=8, state=128, chunk=128)


def test_scan_kernels_compile_for_a_v5e(one_chip, no_compile_cache):
    """Float32 in, bfloat16 operands: forward, the backward's sweep for the
    chunk states and the backward, at blocks the rule finds room for."""
    t, h, p, g, n, l = SCAN.values()
    plan = pallas_ssd.blocks(h, p, g, n, l, jnp.float32, jnp.bfloat16)
    assert plan is not None and plan.vmem_bytes <= pallas_ssd._vmem_bytes() // 2
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    def forward_and_backward(x, dt, a, b, c, dy):
        y, vjp = jax.vjp(lambda *v: pallas_ssd.ssd_scan(*v, l, jnp.bfloat16), x, dt, a, b, c)
        return y, vjp(dy)

    text = jax.jit(forward_and_backward).lower(
        shape(1, t, h, p), shape(1, t, h), shape(h), shape(1, t, g, n), shape(1, t, g, n),
        shape(1, t, h, p)).compile().as_text()
    for kernel in ("ssd_chunk_fwd", "ssd_chunk_starts", "ssd_chunk_bwd"):
        assert text.count("%" + kernel) >= 1, kernel
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_every_scan_kernel_of_a_checkpointed_mamba_mixer_carries_its_scope(
        one_chip, no_compile_cache, monkeypatch):
    """`nemotron_h.forward` checkpoints the mixer: the gradient program runs
    the forward kernel twice (forward, the block's recomputation), the sweep
    and the backward kernel once — and `benchmark/drivers/resident_lm_share.py::scope_map` finds their time
    by `mamba/ssd` in each one's `op_name`, or `ssm_scan_roofline` divides a
    fixed floor by a scope that lost its kernels."""
    from model_zoo.transformer import nemotron_h

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # the route asks
    cfg = nemotron_h.Config(num_hidden_layers=1, hybrid_override_pattern="M")
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups, cfg.ssm_state_size,
            cfg.chunk_size) == tuple(SCAN.values())[1:]
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

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        p, shape(1, SCAN["tokens"], c)).compile().as_text()
    calls = re.findall(r"^\s*%?(ssd_[\w.]+) = ", text, re.M)
    assert sorted(re.sub(r"\.\d+$", "", name) for name in calls) == [
        "ssd_chunk_bwd", "ssd_chunk_fwd", "ssd_chunk_fwd", "ssd_chunk_starts"]
    from benchmark import common
    scope_map = common.load_module("drivers", "resident_lm_share").scope_map
    scopes = scope_map(text, common.load_module("flops", "nemotron_h").SCOPES)
    assert [scopes.get(name) for name in calls] == ["nemotron_h/mamba/ssd"] * 4


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
}


@pytest.mark.parametrize("model", sorted(RECOMPUTED_ATTENTION))
def test_a_recomputed_layer_runs_the_flash_forward_once_under_its_scope(
        model, one_chip, no_compile_cache, monkeypatch):
    """`forward` checkpoints its layers with `pallas_attention.
    KEEP_RESIDUALS`: the gradient program at the cell's tokens compiles with
    one `flash_attention_fwd` call a block (two under the plain checkpoint)
    and ONE `flash_attention_bwd` — no `bwd_dq`, no `bwd_dkv`: a head's keys
    fit VMEM — `_swa_fwd` and `_swa_bwd` in a windowed block, and `scope_map`
    finds each under the block's own `attn` scope: the benchmark's `mla_ms`,
    `mtp_ms`, `swa_ms` and the name-prefix readers (`mla_attn_ms`,
    `gqa_attn_ms`, `swa_attn_ms`, `global_attn_ms`) read them there."""
    import importlib

    from benchmark import common

    module, config, length, blocks = RECOMPUTED_ATTENTION[model]
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
    text = jax.jit(jax.value_and_grad(loss)).lower(params, variables, tokens).compile().as_text()
    calls = re.findall(r"^\s*%?(flash_attention_[\w.]+) = ", text, re.M)
    kinds = [re.sub(r"\.\d+$", "", name) for name in calls]
    scope_map = common.load_module("drivers", "resident_lm_share").scope_map
    found = scope_map(text, common.load_module("flops", module).SCOPES)
    assert sorted((kind, found.get(name)) for kind, name in zip(kinds, calls)) == sorted(
        (prefix + part, scope) for scope, prefix in blocks for part in ("fwd", "bwd"))
