"""The flash backward's two routes (`bwd_route`: one kernel with a head's keys
resident in VMEM, or the two that stream them) and the forward's two
(`fwd_route`: the head's keys resident and a q block's own loop over its kv
blocks, or a grid step a pair), held to each other bit for bit, and a mask
that is data (`keep`) on all (`ops/pallas_attention.py`), in interpret mode on
the CPU. A file of its own so that three xdist workers
share the kernel's cases (`tests/test_pallas_attention.py`,
`tests/test_pallas_attention_window.py`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops.attention import full_attention
from elasticdl_tpu.ops.pallas_attention import can_flash, flash_attention
from tests.conftest import equations, pallas_calls
from tests.test_pallas_attention import (  # noqa: F401  (bwd_log: a fixture)
    RECOMPUTED, _kept, _recomputed_layer, bwd_log, take_route)

# the forward's blocks at a head of 256: its own, not the backward's (1024, 512)
FWD_BLOCKS_256 = (1024, 1024)
from tests.test_pallas_attention_window import _kernel_grids

# (T, heads, key-value heads, head size, block_q, block_k, causal, window,
#  (q_offset, kv_offset), with a cotangent on the logsumexp)
BACKWARD = {
    "mha": (64, 2, 2, 16, 16, 16, True, None, (0, 0), False),
    "acausal": (64, 2, 2, 16, 16, 32, False, None, (0, 0), False),
    "one_block": (32, 2, 2, 16, 32, 32, True, None, (0, 0), False),     # ONE kv block a q block
    "group16": (64, 32, 2, 16, 32, 16, True, None, (0, 0), True),        # Nemotron's 32 on 2
    "head256": (64, 2, 1, 256, 32, 16, True, None, (0, 0), False),       # two diagonal blocks
    "window_in_a_block": (96, 4, 2, 16, 32, 32, True, 5, (0, 0), True),  # the band in ONE kv block
    "window_a_block": (96, 4, 1, 16, 16, 16, True, 16, (0, 0), False),
    "window_off_block": (96, 8, 2, 16, 16, 32, True, 40, (0, 0), True),  # whole blocks in the band
    "window_wide": (128, 2, 2, 16, 16, 16, True, 50, (0, 0), False),
    "offsets": (64, 2, 2, 16, 16, 16, True, None, (64, 32), True),       # ring attention's
    "offsets_before": (64, 2, 2, 16, 16, 16, True, None, (0, 48), True), # q blocks seeing NO key
    "offsets_unaligned": (64, 4, 2, 16, 32, 16, True, None, (40, 8), False),
}


def _backward_case(name):
    from elasticdl_tpu.ops.pallas_attention import flash_attention_lse

    t, heads, kv_heads, head, bq, bk, causal, window, (q_off, kv_off), with_lse = BACKWARD[name]
    r = np.random.RandomState(31)
    draw = lambda *shape: jnp.asarray(r.randn(*shape) * 0.5, jnp.float32)
    q, k, v = draw(1, t, heads, head), draw(1, t, kv_heads, head), draw(1, t, kv_heads, head)
    probe, probe_lse = draw(1, t, heads, head), draw(1, heads, t) * float(with_lse)

    def weigh(out, lse):
        return jnp.sum(probe * out) + jnp.sum(probe_lse * jnp.where(lse > -1e29, lse, 0.0))

    def flash(q, k, v):
        # traced offsets, as ring attention passes them (a window takes none)
        offsets = {} if window is not None else dict(
            q_offset=jnp.int32(q_off), kv_offset=jnp.int32(kv_off))
        if with_lse:
            return weigh(*flash_attention_lse(q, k, v, causal=causal, window=window, block_q=bq,
                                              block_k=bk, interpret=True, **offsets))
        return jnp.sum(probe * flash_attention(q, k, v, causal=causal, window=window, block_q=bq,
                                               block_k=bk, interpret=True, **offsets))

    def dense(q, k, v):
        group = heads // kv_heads
        kk, vv = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                       precision=jax.lax.Precision.HIGHEST) * head ** -0.5
        i, j = q_off + jnp.arange(t)[:, None], kv_off + jnp.arange(t)[None, :]
        mask = (j <= i) if causal else jnp.ones((t, t), bool)
        if window is not None:
            mask &= j > i - window
        s = jnp.where(mask, s, -jnp.inf)
        rows = jnp.any(mask, axis=1)[None, None, :, None]        # a row with no key: zeros
        p = jnp.where(rows, jax.nn.softmax(jnp.where(rows, s, 0.0), axis=-1), 0.0)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, vv, precision=jax.lax.Precision.HIGHEST)
        lse = jnp.where(rows[..., 0], jax.nn.logsumexp(jnp.where(rows, s, 0.0), axis=-1), 0.0)
        return weigh(out, lse)

    return flash, dense, (q, k, v)


@pytest.mark.parametrize("name", sorted(BACKWARD))
def test_the_resident_backward_is_the_split_one_to_the_bit(name, monkeypatch):
    """dq, dk, dv by the one kernel — a head's k and v resident, a pair's
    score block computed once — against a dense mask, and against the dq and
    dkv kernels bit for bit: the same operands, the same order of sums."""
    flash, dense, args = _backward_case(name)
    got = {}
    for route in ("resident", "split"):
        take_route(monkeypatch, route)
        jaxpr = jax.make_jaxpr(jax.grad(flash, argnums=(0, 1, 2)))(*args).jaxpr
        names = sorted(n.replace("swa_", "") for n in _kernel_grids(jaxpr))
        assert names == {"resident": ["flash_attention_bwd", "flash_attention_fwd"],
                         "split": ["flash_attention_bwd_dkv", "flash_attention_bwd_dq",
                                   "flash_attention_fwd"]}[route]
        got[route] = jax.grad(flash, argnums=(0, 1, 2))(*args)
    want = jax.grad(dense, argnums=(0, 1, 2))(*args)
    for a, b, c in zip(got["resident"], got["split"], want):
        assert float(jnp.max(jnp.abs(c))) > 1e-3
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-4, rtol=1e-4)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# (keys, head, dtype) -> the route on a v5e's 128 MiB of VMEM at the blocks the
# plan gives, and the buffers it gives each of the head's k, v, dk and dv: the
# four language-model cells of BENCHMARK.json, then what fits in one buffer
# each and not in the pipeline's two (PR 63; until then `split`), then what
# does not fit
ROUTES = {
    "olmoe-1b-7b.resident-4k": (4096, 128, jnp.bfloat16, "resident", 2),
    "nemotron-3-nano-30b-a3b.resident-8k": (8192, 128, jnp.bfloat16, "resident", 2),
    "glm-4.7-flash.resident-8k": (8192, 256, jnp.bfloat16, "resident", 2),
    "mellum2-12b-a2.5b.resident-16k": (16384, 128, jnp.bfloat16, "resident", 2),
    "32k_keys": (32768, 128, jnp.bfloat16, "resident", 1),
    "lfm2-8b-a1b.resident-32k": (32768, 64, jnp.bfloat16, "resident", 1),
    "16k_keys_of_256": (16384, 256, jnp.bfloat16, "resident", 1),
    "16k_keys_float32": (16384, 128, jnp.float32, "resident", 1),
    "64k_keys": (65536, 128, jnp.bfloat16, "split", 1),
    "32k_keys_of_256": (32768, 256, jnp.bfloat16, "split", 1),
    "32k_keys_float32": (32768, 128, jnp.float32, "split", 1),
}


@pytest.mark.parametrize("name", sorted(ROUTES))
def test_the_backward_route_follows_the_head_s_bytes_and_logs_once_a_shape(
        name, bwd_log, monkeypatch):
    from elasticdl_tpu.ops import pallas_attention as pa

    take_route(monkeypatch, "resident")            # a described v5e
    t_k, head, dtype, want, buffers = ROUTES[name]
    shape = (1, t_k, 4, head)
    bq, bk = pa._plan_blocks(shape, shape, None, None, dtype=dtype)
    plan = pa.bwd_route(t_k, head, dtype, bq, bk)
    assert (plan.route, plan.buffers) == (want, buffers)
    assert (plan.vmem_bytes <= plan.vmem_limit) == (want == "resident")
    assert plan.vmem_limit == (128 << 20) * 3 // 4
    # k, v, dk, dv in the plan's buffers and the two float32 accumulators at
    # least, a head under a lane tile counted as a whole one
    assert plan.vmem_bytes > t_k * max(head, 128) * (4 * buffers * jnp.dtype(dtype).itemsize + 8)
    assert pa.bwd_route(t_k, head, dtype, bq, bk) == plan
    # (a record that also propagates to the root logger is listed twice)
    lines = list({id(r): r.getMessage() for r in bwd_log.records
                  if "backward" in r.getMessage()}.values())
    assert len(lines) == 1 and f"takes the {want} route" in lines[0]
    assert f"{t_k} keys, head {head}" in lines[0]
    assert f"{('one buffer', 'two buffers')[buffers - 1]} each" in lines[0]
    # a smaller chip: the same function, the other answer
    monkeypatch.setattr(pa, "_vmem_bytes", lambda: 16 << 20)
    assert pa.bwd_route(t_k, head, dtype, bq, bk).route == "split"


# (keys, q's and k's head, v's, a data mask, the backward's blocks) -> the
# bytes the parent of PR 63 planned on a v5e, where it planned `resident`:
# every language-model cell's attention but LFM2's at 32 768 keys (OLMoE's and
# Ouro's; Nemotron's; GLM's; Mellum2's and Trinity's, full and sliding; Phi's
# 64 | 128; Xing's 192 | 128; Keye's with its mask; Kimi's 192 | 128; LFM2's
# at half its tokens), written down from that commit's `_bwd_plan`
TWO_BUFFER_PLANS = {
    (4096, 128, 128, False, 1024, 1024): 40894464,
    (8192, 128, 128, False, 1024, 1024): 53477376,
    (8192, 256, 256, False, 1024, 512): 72351744,
    (16384, 128, 128, False, 1024, 1024): 78643200,
    (8192, 64, 128, False, 1024, 1024): 53477376,
    (4096, 192, 128, False, 1024, 512): 38535168,
    (16384, 128, 128, True, 512, 1024): 83886080,
    (16384, 192, 128, False, 1024, 512): 95158272,
    (16384, 64, 64, False, 1024, 1024): 78643200,
}


@pytest.mark.parametrize("shape", sorted(TWO_BUFFER_PLANS), ids=lambda s: "-".join(map(str, s)))
def test_a_head_that_fits_in_two_buffers_plans_what_it_planned_before_one_was_an_answer(shape):
    """No other cell's plan moved: where the pipeline's two buffers fit, the
    route, the bytes and the buffers are the parent's, so the call is too."""
    from elasticdl_tpu.ops import pallas_attention as pa

    t_k, head, v_dim, keep, bq, bk = shape
    assert pa._plan_blocks((1, t_k, 4, head), (1, t_k, 4, head), None, None,
                           dtype=jnp.bfloat16, keep=keep) == (bq, bk)
    plan = pa._bwd_plan(t_k, head, "bfloat16", bq, bk, 128 << 20, keep, v_dim)
    assert plan == pa.Plan("resident", TWO_BUFFER_PLANS[shape], 96 << 20, 2)


def test_every_resident_flash_shape_of_the_aot_file_is_held_to_its_two_buffer_plan():
    from tests.test_kernels_aot import FLASH

    two_buffers = {(t, d, d, False, *blocks)
                   for (_, t, _, _, d, _), blocks, route, buffers in FLASH.values()
                   if (route, buffers) == ("resident", 2)}
    assert two_buffers and two_buffers <= set(TWO_BUFFER_PLANS)


def _whole_head_buffering(jaxpr):
    """{kernel name: the pipeline modes its BlockSpecs name} of a jaxpr."""
    out = {}

    def note(eqn):
        if eqn.primitive.name == "pallas_call":
            out[eqn.params["name"]] = [
                m.pipeline_mode.buffer_count
                for m in eqn.params["grid_mapping"].block_mappings if m.pipeline_mode is not None]

    equations(jaxpr, note)
    return out


@pytest.mark.parametrize("window", [None, 40], ids=["causal", "window_40"])
def test_the_one_buffer_backward_is_the_split_one_to_the_bit(window, monkeypatch):
    """LFM2's attention small: 4 query heads a key-value head, a head of 64,
    causal (and once under a window). On a chip whose VMEM holds the head's k,
    v, dk and dv in ONE buffer each and not in two, the plan is `resident`
    with one buffer, the ONE backward kernel's four whole-head BlockSpecs say
    `Buffered(1)` — with room for two nothing says anything, as before — and
    dq, dk and dv are the split route's in every element."""
    from elasticdl_tpu.ops import pallas_attention as pa

    t, heads, kv_heads, head, bq, bk = 128, 8, 2, 64, 32, 32
    r = np.random.RandomState(63)
    draw = lambda h: jnp.asarray(r.randn(1, t, h, head) * 0.5, jnp.float32)
    q, k, v, probe = draw(heads), draw(kv_heads), draw(kv_heads), draw(heads)
    two = pa._bwd_plan(t, head, "float32", bq, bk, 1 << 40)
    assert two.buffers == 2
    # three quarters of it are a byte short of what two buffers need
    vmem = {"two": 1 << 40, "one": (two.vmem_bytes - 1) * 4 // 3, "split": 1 << 10}
    want = {"two": ("resident", 2), "one": ("resident", 1), "split": ("split", 1)}
    got = {}
    for name in vmem:
        pa._make_flash.cache_clear()
        monkeypatch.setattr(pa, "_vmem_bytes", lambda name=name: vmem[name])
        plan = pa.bwd_route(t, head, jnp.float32, bq, bk)
        assert (plan.route, plan.buffers) == want[name]
        f = lambda q, k, v: jnp.sum(probe * flash_attention(
            q, k, v, window=window, block_q=bq, block_k=bk, interpret=True))
        jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v).jaxpr
        kind = "swa_" if window else ""
        assert {n: b for n, b in _whole_head_buffering(jaxpr).items() if "bwd" in n} == {
            "two": {f"flash_attention_{kind}bwd": []},
            "one": {f"flash_attention_{kind}bwd": [1, 1, 1, 1]},
            "split": {f"flash_attention_{kind}bwd_dq": [], f"flash_attention_{kind}bwd_dkv": []},
        }[name]
        got[name] = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    pa._make_flash.cache_clear()
    for a, b, c in zip(got["one"], got["split"], got["two"]):
        assert float(jnp.max(jnp.abs(a))) > 1e-3
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))



# ------------------------------------------------------------------ #
# the forward's two routes


def take_fwd_route(monkeypatch, route):
    """The forward's route alone, on a described v5e: the backward stays the
    resident one, so that what differs between two runs is the forward kernel."""
    from elasticdl_tpu.ops import pallas_attention as pa

    take_route(monkeypatch, "resident")
    if route == "streaming":
        monkeypatch.setattr(pa, "fwd_route", lambda *a, **kw: pa.Plan("streaming", 0, 96 << 20))


def _forward_case(name):
    """(f(q, k, v) -> (out, lse), (q, k, v), the rows that see no key) of a
    `BACKWARD` case, or of `keep`: a data mask over 4-on-2 heads."""
    from elasticdl_tpu.ops.pallas_attention import flash_attention_lse

    if name == "keep":
        q, k, v, keep = _keep_case()
        return (lambda q, k, v: flash_attention_lse(
            q, k, v, keep=keep, block_q=32, block_k=32, interpret=True)), (q, k, v), 0
    t, heads, kv_heads, head, bq, bk, causal, window, (q_off, kv_off), _ = BACKWARD[name]
    r = np.random.RandomState(37)
    draw = lambda h: jnp.asarray(r.randn(1, t, h, head) * 0.5, jnp.float32)
    # traced offsets, as ring attention passes them (a window takes none)
    offsets = {} if window is not None else dict(
        q_offset=jnp.int32(q_off), kv_offset=jnp.int32(kv_off))
    f = lambda q, k, v: flash_attention_lse(q, k, v, causal=causal, window=window, block_q=bq,
                                            block_k=bk, interpret=True, **offsets)
    return f, (draw(heads), draw(kv_heads), draw(kv_heads)), max(0, min(t, kv_off - q_off))


FORWARD = sorted(BACKWARD) + ["keep"]


@pytest.mark.parametrize("name", FORWARD)
def test_the_resident_forward_is_the_streaming_one_to_the_bit(name, monkeypatch):
    """Output AND logsumexp by the kernel that holds a head's k and v in VMEM
    and loops over a q block's kv blocks, against the one that takes a grid
    step a pair: the same recurrence on the same blocks in the same order.
    The grids tell the routes apart: the resident one has no kv axis."""
    f, args, unseen = _forward_case(name)
    got = {}
    for route in ("resident", "streaming"):
        take_fwd_route(monkeypatch, route)
        # (JAX keeps a function's trace: a new one for each route)
        (kernel, grid), = _kernel_grids(jax.make_jaxpr(lambda *a: f(*a))(*args).jaxpr).items()
        assert kernel.endswith("_fwd") and len(grid) == {"resident": 3, "streaming": 4}[route]
        got[route] = f(*args)
    for a, b in zip(got["resident"], got["streaming"]):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    out, lse = got["resident"]
    # rows that see no key: zero, and a logsumexp the ring merge reads as nothing
    assert unseen == {"offsets_before": 48}.get(name, 0)
    np.testing.assert_array_equal(np.asarray(out[:, :unseen]), 0.0)
    np.testing.assert_array_equal(np.asarray(lse[..., :unseen]), np.float32(-1e30))
    assert float(jnp.min(lse[..., unseen:])) > -1e3
    assert float(jnp.min(jnp.max(jnp.abs(out[:, unseen:]), axis=-1))) > 0.0


@pytest.mark.parametrize("name", FORWARD)
def test_gradients_through_either_forward_route_are_equal_to_the_bit(name, monkeypatch):
    """The residuals are q, k, v, out and the logsumexp whatever kernel made
    the last two: one backward kernel after either forward, the same dq, dk,
    dv, with a cotangent on both outputs."""
    f, args, unseen = _forward_case(name)
    r = np.random.RandomState(41)
    out, lse = jax.eval_shape(f, *args)
    probe, probe_lse = (jnp.asarray(r.randn(*x.shape), jnp.float32) for x in (out, lse))

    def loss(*a):
        out, lse = f(*a)
        return jnp.sum(probe * out) + jnp.sum(probe_lse[..., unseen:] * lse[..., unseen:])

    got = {}
    for route in ("resident", "streaming"):
        take_fwd_route(monkeypatch, route)
        grids = _kernel_grids(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*args).jaxpr)
        assert sorted(len(g) for g in grids.values()) == {"resident": [3, 3],
                                                          "streaming": [3, 4]}[route]
        got[route] = jax.grad(loss, argnums=(0, 1, 2))(*args)
    for a, b in zip(got["resident"], got["streaming"]):
        assert float(jnp.max(jnp.abs(a))) > 1e-3
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# (batch, tokens, query heads, key-value heads, head, window, a data mask) ->
# the forward's blocks and route on a v5e's 128 MiB: the attention of
# BENCHMARK.json's six language-model cells as their configurations give it,
# then what does not fit
FORWARD_ROUTES = {
    "olmoe-1b-7b.resident-4k": ((2, 4096, 16, 16, 128, None, False), (1024, 1024), "resident"),
    "nemotron-3-nano-30b-a3b.resident-8k": ((1, 8192, 32, 2, 128, None, False), (1024, 1024),
                                            "resident"),
    "glm-4.7-flash.resident-8k": ((1, 8192, 20, 20, 256, None, False), FWD_BLOCKS_256, "resident"),
    "mellum2-12b-a2.5b.resident-16k/full": ((1, 16384, 32, 4, 128, None, False), (1024, 1024),
                                            "resident"),
    "mellum2-12b-a2.5b.resident-16k/sliding": ((1, 16384, 32, 4, 128, 1024, False), (1024, 1024),
                                               "resident"),
    "trinity-mini.resident-16k/sliding": ((1, 16384, 32, 4, 128, 2048, False), (1024, 1024),
                                          "resident"),
    # the q blocks of 512 are the backward's, whose strip sits beside dk and dv
    "keye-vl-2.0-30b-a3b.resident-16k": ((1, 16384, 32, 4, 128, None, True), (1024, 1024),
                                         "resident"),
    # no dk and dv to hold: the forward fits where the backward splits
    "32k_keys": ((1, 32768, 8, 2, 128, None, False), (1024, 1024), "resident"),
    "128k_keys": ((1, 131072, 8, 2, 128, None, False), (1024, 1024), "streaming"),
    "64k_keys_of_256": ((1, 65536, 2, 2, 256, None, False), FWD_BLOCKS_256, "streaming"),
    "64k_keys_float32": ((1, 65536, 2, 2, 128, None, False), (1024, 1024), "streaming"),
}


@pytest.mark.parametrize("name", sorted(FORWARD_ROUTES))
def test_the_forward_route_follows_the_head_s_bytes_and_logs_once_a_shape(
        name, bwd_log, monkeypatch):
    """`fwd_route` from the shapes alone, and the call as it lowers at the
    cell's own shape (traced, nothing runs): the resident forward's grid is
    (B, key-value heads, q blocks x the group's heads)."""
    from elasticdl_tpu.ops import pallas_attention as pa

    take_route(monkeypatch, "resident")            # a described v5e
    (b, t, heads, kv_heads, head, window, keep), blocks, want = FORWARD_ROUTES[name]
    dtype = jnp.float32 if name.endswith("float32") else jnp.bfloat16
    q, k = ((b, t, h, head) for h in (heads, kv_heads))
    assert pa._plan_blocks(q, k, None, None, dtype=dtype, keep=keep, forward=True) == blocks
    plan = pa.fwd_route(t, head, dtype, *blocks, keep=keep)
    assert plan.route == want
    assert (plan.vmem_bytes <= plan.vmem_limit) == (want == "resident")
    assert plan.vmem_limit == (128 << 20) * 3 // 4
    # k and v twice buffered at least, and with a data mask the q block's strip
    assert plan.vmem_bytes > 4 * t * head * jnp.dtype(dtype).itemsize + 2 * blocks[0] * t * keep
    # every shape that takes the resident backward takes the resident forward
    bwd = pa._plan_blocks(q, k, None, None, dtype=dtype, keep=keep)
    assert want == "resident" or pa.bwd_route(t, head, dtype, *bwd, keep=keep).route == "split"

    shaped = lambda shape, dt=dtype: jax.ShapeDtypeStruct(shape, dt)
    jaxpr = jax.make_jaxpr(lambda q, k, v, *plane: flash_attention(
        q, k, v, window=window, keep=plane[0] if plane else None, interpret=False))(
        shaped(q), shaped(k), shaped(k), *([shaped((b, t, t), jnp.int8)] if keep else [])).jaxpr
    (kernel, grid), = _kernel_grids(jaxpr).items()
    assert kernel == "flash_attention_" + ("swa_" if window else "sel_" if keep else "") + "fwd"
    num_q = t // blocks[0]
    assert grid == ((b, kv_heads, num_q * heads // kv_heads) if want == "resident"
                    else (b, heads, num_q, t // blocks[1]))
    lines = list({id(r): r.getMessage() for r in bwd_log.records
                  if "forward" in r.getMessage()}.values())
    assert len(lines) == 1 and f"takes the {want} route" in lines[0]
    assert f"{t} keys, head {head}" in lines[0] and ("a data mask" in lines[0]) == keep
    # a smaller chip: the same function, the other answer
    monkeypatch.setattr(pa, "_vmem_bytes", lambda: 16 << 20)
    assert pa.fwd_route(t, head, dtype, *blocks, keep=keep).route == "streaming"


@pytest.mark.parametrize("route", ["resident", "streaming"])
@pytest.mark.parametrize("case", sorted(RECOMPUTED))
def test_a_kept_layer_holds_one_forward_call_on_either_forward_route(case, route, monkeypatch):
    """A recomputed layer under `KEEP_RESIDUALS` holds ONE forward kernel call
    whichever kernel that is, under a plain checkpoint two."""
    take_fwd_route(monkeypatch, route)
    loss, args = _recomputed_layer(case)
    for wrap, calls in ((_kept, 1), (jax.checkpoint, 2)):
        jaxpr = jax.make_jaxpr(jax.grad(loss(wrap), argnums=(0, 1, 2), has_aux=True))(*args).jaxpr
        assert pallas_calls(jaxpr, "flash_attention_fwd") == calls
        assert len(_kernel_grids(jaxpr)["flash_attention_fwd"]) == {"resident": 3,
                                                                    "streaming": 4}[route]


# ------------------------------------------------------------------ #
# a mask that is data (`keep`)


def _keep_case(t=128, heads=4, kv_heads=2, seed=11, share=0.3):
    """(q, k, v, keep): a random plane that keeps every query's own position
    and NO key of one whole (32 x 32) block below the diagonal."""
    r = np.random.RandomState(seed)
    draw = lambda h: jnp.asarray(r.randn(2, t, h, 16), jnp.float32)
    keep = r.rand(2, t, t) < share
    keep |= np.eye(t, dtype=bool)[None]
    keep[:, 64:96, 0:32] = False
    return draw(heads), draw(kv_heads), draw(kv_heads), jnp.asarray(keep)


def _dense_keep(q, k, v, keep):
    """(out, lse) by a dense mask: j <= i and keep[i, j]."""
    b, t, h, d = q.shape
    k, v = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    mask = jnp.tril(jnp.ones((t, t), bool))[None] & keep
    s = jnp.where(mask[:, None], s, -jnp.inf)
    return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v),
            jax.nn.logsumexp(s, axis=-1))


@pytest.mark.parametrize("route", ["resident", "split"])
@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (8, 2)])
def test_keep_forward_and_both_backward_routes_match_a_dense_mask(route, heads, kv_heads,
                                                                  monkeypatch):
    from elasticdl_tpu.ops.pallas_attention import flash_attention_lse

    take_route(monkeypatch, route)
    q, k, v, keep = _keep_case(heads=heads, kv_heads=kv_heads)
    probe = jnp.asarray(np.random.RandomState(5).randn(*q.shape), jnp.float32)

    def loss(f):
        def value(q, k, v):
            out, lse = f(q, k, v)
            return jnp.sum(probe * out) + jnp.sum(jnp.sin(lse)), (out, lse)
        return jax.value_and_grad(value, argnums=(0, 1, 2), has_aux=True)

    flash = lambda *a: flash_attention_lse(*a, keep=keep, block_q=32, block_k=32,
                                           interpret=True)
    ((_, got), got_grads), ((_, want), want_grads) = loss(flash)(q, k, v), loss(
        lambda *a: _dense_keep(*a, keep))(q, k, v)
    for a, b in zip(got + got_grads, want + want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("route", ["resident", "split"])
def test_keep_of_all_ones_is_the_causal_call_to_the_bit(route, monkeypatch):
    take_route(monkeypatch, route)
    q, k, v, _ = _keep_case(t=96)
    ones = jnp.ones((2, 96, 96), jnp.int8)
    f = lambda keep: jax.value_and_grad(lambda *a: jnp.sum(flash_attention(
        *a, keep=keep, block_q=32, block_k=32, interpret=True) ** 2), argnums=(0, 1, 2))
    for a, b in zip(jax.tree_util.tree_leaves(f(ones)(q, k, v)),
                    jax.tree_util.tree_leaves(f(None)(q, k, v))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("route,keep,want", [
    ("resident", False, {"flash_attention_fwd": (2, 2, 8), "flash_attention_bwd": (2, 2, 8)}),
    ("split", False, {"flash_attention_fwd": (2, 4, 4, 4), "flash_attention_bwd_dq": (2, 4, 4, 4),
                      "flash_attention_bwd_dkv": (2, 2, 4, 8)}),
    ("resident", True, {"flash_attention_sel_fwd": (2, 2, 8),
                        "flash_attention_sel_bwd": (2, 2, 8)}),
    ("split", True, {"flash_attention_sel_fwd": (2, 4, 4, 4),
                     "flash_attention_sel_bwd_dq": (2, 4, 4, 4),
                     "flash_attention_sel_bwd_dkv": (2, 2, 4, 8)}),
])
def test_keep_none_lowers_to_the_kernels_it_always_did(route, keep, want, monkeypatch):
    """Read off the lowered calls: without `keep` the names and grids of
    before, with it names of its own on the SAME grids (no block is skipped for
    being empty of kept keys), and one operand more."""
    take_route(monkeypatch, route)
    q, k, v, plane = _keep_case()
    f = lambda *a: jnp.sum(flash_attention(*a, keep=plane if keep else None, block_q=32,
                                           block_k=32, interpret=True) ** 2)
    jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v).jaxpr
    assert _kernel_grids(jaxpr) == want
    operands = []
    equations(jaxpr, lambda eqn: eqn.primitive.name == "pallas_call"
              and operands.append(len(eqn.invars)))
    # offsets, q, k, v (+ keep); offsets, q, k, v, out, do, lse (+ keep)
    assert sorted(set(operands)) == ([5, 8] if keep else [4, 7])


def test_a_keep_call_plans_smaller_q_blocks_and_counts_its_strip():
    from elasticdl_tpu.ops import pallas_attention as pa

    shape = (1, 16384, 32, 128)
    assert pa._plan_blocks(shape, shape, None, None, dtype=jnp.bfloat16) == (1024, 1024)
    assert pa._plan_blocks(shape, shape, None, None, dtype=jnp.bfloat16, keep=True) == (512, 1024)
    assert pa._plan_blocks(shape, shape, None, None, dtype=jnp.bfloat16, keep=True,
                           forward=True) == (1024, 1024)
    vmem = 128 << 20
    plan = lambda bq, keep: pa._bwd_plan(16384, 128, "bfloat16", bq, 1024, vmem, keep)
    assert plan(1024, False).route == "resident" and plan(512, True).route == "resident"
    assert plan(1024, False).buffers == plan(512, True).buffers == 2
    # the strip at 1024 rows leaves the head's blocks ONE buffer each (PR 63;
    # `split` until then): 512 is what the call plans, and there two fit
    assert (plan(1024, True).route, plan(1024, True).buffers) == ("resident", 1)
    assert plan(2048, True).route == "split"
    assert plan(512, True).vmem_bytes - plan(512, False).vmem_bytes \
        == 2 * 512 * 16384 + 4 * 512 * 1024
    # an int8 tile has 32 rows: a sequence with no such block is declined
    assert pa._plan_blocks((1, 48, 2, 16), (1, 48, 2, 16), None, None, keep=True) is None


def test_keep_takes_no_window_and_no_offsets(monkeypatch):
    q, k, v, keep = _keep_case(t=64)
    keep = keep[:, :64, :64]
    with pytest.raises(ValueError, match="without a window"):
        flash_attention(q, k, v, keep=keep, window=8, interpret=True)
    with pytest.raises(ValueError, match="without a window"):
        flash_attention(q, k, v, keep=keep, q_offset=64, interpret=True)
    with pytest.raises(ValueError, match="no head axis"):
        flash_attention(q, k, v, keep=keep[:, None], interpret=True)
    with pytest.raises(ValueError, match="int8 or bool"):
        flash_attention(q, k, v, keep=keep.astype(jnp.float32), interpret=True)
    monkeypatch.setenv("EDL_FLASH", "1")
    monkeypatch.setenv("EDL_FLASH_INTERPRET", "1")
    assert can_flash(q.shape, k.shape, keep=True)
    assert not can_flash(q.shape, k.shape, keep=True, window=8)
    assert not can_flash(q.shape, k.shape, keep=True, q_offset=64)
    assert not can_flash(q.shape, k.shape, keep=True, kv_offset=jnp.int32(0))
    # `full_attention` then takes its XLA path, with the same mask
    jaxpr = jax.make_jaxpr(lambda *a: full_attention(*a, keep=keep, window=8))(q, k, v).jaxpr
    assert not _kernel_grids(jaxpr)


def test_full_attention_passes_its_keep_to_the_kernel(monkeypatch):
    monkeypatch.setenv("EDL_FLASH", "1")
    monkeypatch.setenv("EDL_FLASH_INTERPRET", "1")
    monkeypatch.setattr("elasticdl_tpu.ops.pallas_attention.SEL_BLOCK_Q", 32)
    monkeypatch.setattr("elasticdl_tpu.ops.pallas_attention.DEFAULT_BLOCK_K", 32)
    q, k, v, keep = _keep_case()
    jaxpr = jax.make_jaxpr(lambda *a: full_attention(*a, keep=keep, with_lse=True))(q, k, v).jaxpr
    assert pallas_calls(jaxpr, "flash_attention_sel_fwd") == 1
    got, want = full_attention(q, k, v, keep=keep, with_lse=True), _dense_keep(q, k, v, keep)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)
