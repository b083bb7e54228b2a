"""REHEARSAL, no chip: the grouped matmul's three forms compile for a
described v5e at the widths the benchmark's three language-model cells run
(`ops/pallas_gmm.py`; the on-chip-measurement guide, section 2), and the
flash-attention kernels at the four language-model cells' shapes, the ONE
backward kernel with a key-value head's keys resident in VMEM — at 32 768
keys in ONE buffer for each of k, v, dk and dv — and at a head that does not
fit so either (65 536 keys), the two that stream it
(`ops/pallas_attention.py`: the block plan follows the head size, `bwd_route`
the head's bytes), and the
state-space scan's three kernels at the Nemotron cell's shapes
(`ops/pallas_ssd.py`) and the delta rule's two at the Kimi cell's
(`ops/pallas_delta_rule.py`), and the depthwise convolution's two at both
cells' planes (`ops/pallas_conv1d.py`) — the same kernels inside the zoo's checkpointed layers,
under the scopes the benchmark reads them by, are
`tests/test_kernels_aot_layers.py`'s; and
the pull-back of the Keye cell's index scores (`ops/sparse_attention.py::
index_score_bwd`) at that cell's shape; and xDeepFM's CIN kernels
(`ops/pallas_cin.py`) at the Criteo cell's. What
interpret mode cannot see — a block Mosaic refuses, more VMEM than a kernel
may use — fails here and costs no chip time. Nothing runs: no time, no result.

The topology is described inside a fixture, never at import: only the xdist
workers that are given this file and `tests/test_kernels_aot_layers.py` load
libtpu (the driver's command sets `ALLOW_MULTIPLE_LIBTPU_LOAD=1`)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from elasticdl_tpu.ops import pallas_conv1d, pallas_delta_rule, pallas_gmm, pallas_ssd

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
    # trinity-mini.resident-16k: 16 of 128 held at OLMoE's expert shape, a pass
    # of 32 768 rows (twice the even share of 131 072 pairs)
    "trinity_up": (32768, 2048, 1024, 16),
    "trinity_down": (32768, 1024, 2048, 16),
    # xing4.0-29b-a4b.resident-4k: 8 of 64 held at hidden 3584, a pass of 4096
    # rows (twice the even share of 16 384 pairs)
    "xing_up": (4096, 3584, 1024, 8),
    "xing_down": (4096, 1024, 3584, 8),
}


# what is read here is what XLA:TPU and Mosaic emit for the chip: these compiles
# keep the optimisations `tests/conftest.py` turns off for the CPU's programs
pytestmark = pytest.mark.usefixtures("xla_optimises")


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
# blocks for the backward, the backward's route and the buffers it gives each
# of the head's k, v, dk and dv: the attention of BENCHMARK.json's
# language-model cells, then a head that fits VMEM whole in one buffer each
# and not in the pipeline's two (PR 63), then one that does not fit at all
# (its forward, with no dk and dv to hold, still does)
FLASH = {
    "olmoe-1b-7b.resident-4k": ((2, 4096, 16, 16, 128, None), (1024, 1024), "resident", 2),
    "nemotron-3-nano-30b-a3b.resident-8k": ((1, 8192, 32, 2, 128, None), (1024, 1024), "resident", 2),
    # the plan halves the backward's key block at head 256: at (1024, 1024) the
    # dq kernel asked for 16.9 MB of Mosaic's default 16 (PR 32); the forward's
    # blocks are its own (`FORWARD_BLOCKS`)
    "glm-4.7-flash.resident-8k": ((1, 8192, 20, 20, 256, None), (1024, 512), "resident", 2),
    "mellum2-12b-a2.5b.resident-16k/full": ((1, 16384, 32, 4, 128, None), (1024, 1024), "resident", 2),
    "mellum2-12b-a2.5b.resident-16k/sliding": ((1, 16384, 32, 4, 128, 1024), (1024, 1024), "resident", 2),
    # three key blocks a query block where Mellum2's window of 1024 has two;
    # its full layer is Mellum2's (unrotated q and k are no other shape)
    "trinity-mini.resident-16k/sliding": ((1, 16384, 32, 4, 128, 2048), (1024, 1024), "resident", 2),
    "32k_keys": ((1, 32768, 8, 2, 128, None), (1024, 1024), "resident", 1),
    "32k_keys/sliding": ((1, 32768, 8, 2, 128, 1024), (1024, 1024), "resident", 1),
    "64k_keys": ((1, 65536, 8, 2, 128, None), (1024, 1024), "split", 1),
    "64k_keys/sliding": ((1, 65536, 8, 2, 128, 1024), (1024, 1024), "split", 1),
}
# the forward's blocks where they are not the backward's
FORWARD_BLOCKS = {"glm-4.7-flash.resident-8k": (1024, 1024)}


@pytest.mark.parametrize("name", sorted(FLASH))
def test_flash_kernels_compile_for_a_v5e(name, one_chip, no_compile_cache):
    """Forward and backward in bfloat16 at the blocks the plan gives: the
    forward with the key-value head's k and v resident in VMEM at every one of
    these shapes, ONE backward kernel where its k, v, dk and dv fit the VMEM
    `bwd_route` allows it (`vmem_limit_bytes`: Mosaic's default 16 MB hold
    none of these heads) — in two buffers each, or at 32 768 keys in one: the
    compiler takes what the plan says fits — the dq and the dkv kernel where
    they do not."""
    from elasticdl_tpu.ops import pallas_attention

    (b, t, h, hkv, d, window), blocks, route, buffers = FLASH[name]
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, t, hkv, d), jnp.bfloat16, sharding=one_chip)
    assert pallas_attention._plan_blocks(q.shape, k.shape, None, None, dtype=jnp.bfloat16) == blocks
    plan = pallas_attention.bwd_route(t, d, jnp.bfloat16, *blocks)
    assert (plan.route, plan.buffers) == (route, buffers)
    forward = FORWARD_BLOCKS.get(name, blocks)
    assert pallas_attention._plan_blocks(q.shape, k.shape, None, None, dtype=jnp.bfloat16,
                                         forward=True) == forward
    assert pallas_attention.fwd_route(t, d, jnp.bfloat16, *forward).route == "resident"

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
def test_flash_kernels_at_two_head_widths_compile_for_a_v5e(route, one_chip, no_compile_cache,
                                                            monkeypatch):
    """xing4.0-29b-a4b.resident-4k's attention: 32 heads whose q and k are 192
    wide (no multiple of the 128-lane tile: a full-dimension block, two tiles in
    VMEM) and whose v is 128, 4096 keys. One forward and one backward kernel
    with the head resident; on a chip of 32 MiB the streaming forward and the
    split route's two kernels, the backward's key block 512 either way."""
    from elasticdl_tpu.ops import pallas_attention

    if route == "split":
        monkeypatch.setattr(pallas_attention, "_vmem_bytes", lambda: 32 << 20)
    shape = lambda d: jax.ShapeDtypeStruct((1, 4096, 32, d), jnp.bfloat16, sharding=one_chip)
    q, v = shape(192), shape(128)
    assert pallas_attention._plan_blocks(q.shape, q.shape, None, None,
                                         dtype=jnp.bfloat16) == (1024, 512)
    assert pallas_attention.bwd_route(4096, 192, jnp.bfloat16, 1024, 512,
                                      v_dim=128).route == route
    assert pallas_attention.fwd_route(4096, 192, jnp.bfloat16, 1024, 1024, v_dim=128).route == (
        "resident" if route == "resident" else "streaming")

    def forward_and_backward(q, k, v, do):
        out, vjp = jax.vjp(lambda q, k, v: pallas_attention.flash_attention(
            q, k, v, causal=True, interpret=False), q, k, v)
        return out, vjp(do)

    text = jax.jit(forward_and_backward).lower(q, q, v, v).compile().as_text()
    calls = re.findall(r"^\s*%\w*?(flash_attention_[a-z_]*?)_*\.\d+ = .*tpu_custom_call", text, re.M)
    assert sorted(calls) == ["flash_attention_" + part for part in (
        ["bwd", "fwd"] if route == "resident" else ["bwd_dkv", "bwd_dq", "fwd"])]
    assert text.count('custom_call_target="tpu_custom_call"') == len(calls)


@pytest.mark.parametrize("route", ["resident", "split"])
def test_masked_flash_kernels_compile_for_a_v5e(route, one_chip, no_compile_cache, monkeypatch):
    """keye-vl-2.0-30b-a3b.resident-16k's attention: 32/4 heads of 128, 16 384
    keys and an int8 `keep` plane as a fifth operand, with the logsumexp
    returned. The plan gives the backward q blocks of 512 so that it
    holds the q block's (512, 16 384) strip of the mask beside the head's k,
    v, dk and dv, as the resident forward does beside k and v; on a chip of 32
    MiB the streaming forward and the split route's two kernels take the mask
    tile by tile."""
    from elasticdl_tpu.ops import pallas_attention

    b, t, h, hkv, d = 1, 16384, 32, 4, 128
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, t, hkv, d), jnp.bfloat16, sharding=one_chip)
    keep = jax.ShapeDtypeStruct((b, t, t), jnp.int8, sharding=one_chip)
    assert pallas_attention._plan_blocks(q.shape, k.shape, None, None, dtype=jnp.bfloat16,
                                         keep=True) == (512, 1024)
    if route == "split":
        pallas_attention._make_flash.cache_clear()
        monkeypatch.setattr(pallas_attention, "_vmem_bytes", lambda: 32 << 20)
    assert pallas_attention.bwd_route(t, d, jnp.bfloat16, 512, 1024, keep=True).route == route
    assert pallas_attention._plan_blocks(q.shape, k.shape, None, None, dtype=jnp.bfloat16,
                                         keep=True, forward=True) == (1024, 1024)
    assert pallas_attention.fwd_route(t, d, jnp.bfloat16, 1024, 1024, keep=True).route == (
        "resident" if route == "resident" else "streaming")

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


# kimi-linear-48b-a3b.resident-16k's recurrence: one sequence of 16 384
# tokens, 32 heads of 128 key and value channels, chunks of 64 in blocks of 4
DELTA_RULE = dict(tokens=16384, heads=32, head_dim=128, chunk=64, chunks_per_block=4)


def test_delta_rule_kernels_compile_for_a_v5e(one_chip, no_compile_cache):
    """Float32 in, bfloat16 operands: the forward and the backward, a visit a
    (head, block of 4 chunks), inside the VMEM the rule finds room for."""
    t, h, d, l, n = DELTA_RULE.values()
    plan = pallas_delta_rule.blocks(d, d, l, n)
    assert plan is not None and plan.vmem_bytes <= pallas_delta_rule._vmem_bytes() // 2
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    def forward_and_backward(q, k, v, g, beta, d_o, d_last):
        (o, last), vjp = jax.vjp(
            lambda *a: pallas_delta_rule.delta_rule_kernels(*a, l, n, jnp.bfloat16),
            q, k, v, g, beta)
        return o, last, vjp((d_o, d_last))

    plane = shape(1, t, h, d)
    text = jax.jit(forward_and_backward).lower(
        plane, plane, plane, plane, shape(1, t, h), plane, shape(1, h, d, d)).compile().as_text()
    for kernel in ("delta_rule_fwd", "delta_rule_bwd"):
        assert text.count("%" + kernel) >= 1, kernel
    assert text.count('custom_call_target="tpu_custom_call"') == 2


# qwen3-next-80b-a3b.resident-16k's recurrence: the SCALAR form, one sequence
# of 16 384 tokens, 16 key heads read by 32 value heads of 128, chunks of 64 in
# blocks of 4
SCALAR_DELTA_RULE = dict(tokens=16384, key_heads=16, value_heads=32, head_dim=128, chunk=64,
                         chunks_per_block=4)


def test_scalar_delta_rule_kernels_compile_for_a_v5e(one_chip, no_compile_cache):
    """Float32 in, bfloat16 operands: the forward and the backward on the grid
    (sequence, key head, block, value head of the key head) — q, k, dq and dk
    at the KEY heads' width, g and β as (T, 32) planes — inside the VMEM the
    rule finds room for. Mosaic takes the r states side by side in the scratch
    and the key head's dq, dk added to over its value heads' visits."""
    t, hk, hv, d, l, n = SCALAR_DELTA_RULE.values()
    plan = pallas_delta_rule.scalar_blocks(d, d, l, n, hv // hk)
    assert plan is not None and plan.vmem_bytes <= pallas_delta_rule._vmem_bytes() // 2
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    def forward_and_backward(q, k, v, g, beta, d_o, d_last):
        (o, last), vjp = jax.vjp(
            lambda *a: pallas_delta_rule.delta_rule_scalar_kernels(*a, l, n, jnp.bfloat16),
            q, k, v, g, beta)
        return o, last, vjp((d_o, d_last))

    keys, values, rows = shape(1, t, hk, d), shape(1, t, hv, d), shape(1, t, hv)
    text = jax.jit(forward_and_backward).lower(
        keys, keys, values, rows, rows, values, shape(1, hv, d, d)).compile().as_text()
    for kernel in ("delta_rule_scalar_fwd", "delta_rule_scalar_bwd"):
        assert text.count("%" + kernel) >= 1, kernel
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "%delta_rule_fwd" not in text and "%delta_rule_bwd" not in text


# the depthwise convolutions of kimi-linear-48b-a3b.resident-16k's KDA layers
# (q, k, v: no bias) and of nemotron-3-nano-30b-a3b.resident-8k's Mamba layers
# (xBC, a bias): (tokens, channels, a bias), 4 taps
CONV = {"kimi-linear-48b-a3b.resident-16k": (16384, 4096, False),
        "nemotron-3-nano-30b-a3b.resident-8k": (8192, 6144, True)}


@pytest.mark.parametrize("cell", sorted(CONV))
def test_causal_conv1d_kernels_compile_for_a_v5e(cell, one_chip, no_compile_cache):
    """Float32 planes in the projection's layout: the forward and the
    pull-back at the blocks the rule gives, one call each and nothing of the
    plane's size around them but the operands and results."""
    t, ch, bias = CONV[cell]
    plan = pallas_conv1d.blocks(t, ch, 4)
    assert plan == (512, 512)
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    def forward_and_backward(x, w, b, du):
        conv = lambda x, w, b: pallas_conv1d.causal_conv1d_kernels(x, w, b if bias else None, plan)
        u, vjp = jax.vjp(conv, x, w, b)
        return u, vjp(du)

    exe = jax.jit(forward_and_backward).lower(
        shape(1, t, ch), shape(4, ch), shape(ch), shape(1, t, ch)).compile()
    text = exe.as_text()
    for kernel in ("causal_conv1d_fwd", "causal_conv1d_bwd"):
        assert len(re.findall(rf"^\s*%{kernel}\.\d+ = .*tpu_custom_call", text, re.M)) == 1
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert not re.search(r" (convolution|copy)\(", text)
    assert exe.memory_analysis().temp_size_in_bytes < 4 * t * ch // 8
