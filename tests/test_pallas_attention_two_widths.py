"""The flash kernels at TWO head widths — q and k one (Dqk), v and the output
another (Dv): latent attention's 192 beside 128 — in interpret mode on the CPU
against `full_attention`'s XLA path, and at Dv == Dqk against what the parent
of PR 48 traced at every LM cell's shape (`tests/flash_signatures_pr46.json`:
kernel names, grids, block shapes and both plans, written by the same
`signature` from commit 543450b)."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import pallas_attention as pa
from elasticdl_tpu.ops.attention import full_attention
from tests.test_pallas_attention import bwd_log  # noqa: F401  (a fixture)
from tests.test_pallas_attention_routes import FORWARD_ROUTES

WIDTHS = [(192, 128), (256, 128), (64, 128)]
# (heads, key-value heads, window, a data mask)
VARIANTS = {"causal": (2, 2, None, False), "window": (2, 2, 24, False),
            "keep": (2, 2, None, True), "grouped_query": (4, 2, None, False)}
T = 64


def _operands(dqk, dv, variant, seed=0):
    heads, kv_heads, window, keep = VARIANTS[variant]
    r = np.random.default_rng(seed)
    make = lambda h, d: jnp.asarray(r.normal(size=(1, T, h, d)), jnp.float32)
    q, k, v, g = make(heads, dqk), make(kv_heads, dqk), make(kv_heads, dv), make(heads, dv)
    g_lse = jnp.asarray(r.normal(size=(1, heads, T)), jnp.float32)
    plane = None
    if keep:        # every row keeps its own position
        plane = jnp.asarray(r.random((1, T, T)) < 0.5) | jnp.eye(T, dtype=bool)[None]
    return q, k, v, g, g_lse, window, plane


def _results(attend, q, k, v, g, g_lse):
    """(out, lse, dq, dk, dv) of `attend(q, k, v) -> (out, lse)` under the
    cotangents (g, g_lse)."""
    (out, lse), vjp = jax.vjp(attend, q, k, v)
    return dict(zip(("out", "lse", "dq", "dk", "dv"), (out, lse) + vjp((g, g_lse))))


@functools.lru_cache(maxsize=None)
def both_paths(dqk, dv, variant):
    q, k, v, g, g_lse, window, plane = _operands(dqk, dv, variant)
    want = _results(lambda q, k, v: full_attention(
        q, k, v, causal=True, window=window, keep=plane, with_lse=True), q, k, v, g, g_lse)
    with pa.interpret_mode():
        got = _results(lambda q, k, v: pa.flash_attention_lse(
            q, k, v, causal=True, window=window, keep=plane, block_q=32, block_k=32),
            q, k, v, g, g_lse)
    return got, want


@pytest.mark.parametrize("what", ["out", "lse", "dq", "dk", "dv"])
@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("dqk,dv", WIDTHS)
def test_kernels_match_the_xla_path(dqk, dv, variant, what):
    got, want = both_paths(dqk, dv, variant)
    heads, kv_heads = VARIANTS[variant][:2]
    shape = {"out": (1, T, heads, dv), "lse": (1, heads, T), "dq": (1, T, heads, dqk),
             "dk": (1, T, kv_heads, dqk), "dv": (1, T, kv_heads, dv)}[what]
    assert got[what].shape == want[what].shape == shape
    np.testing.assert_allclose(got[what], want[what], rtol=2e-5, atol=2e-5)


def test_the_xla_path_by_hand_at_two_widths():
    """Grouped queries, v narrower than k: softmax(q·kT / sqrt(Dqk)) v."""
    q, k, v, *_ = _operands(24, 8, "grouped_query", seed=3)
    out = np.asarray(full_attention(q, k, v, causal=True))
    assert out.shape == (1, T, 4, 8)
    for head in range(4):
        s = np.asarray(q)[0, :, head] @ np.asarray(k)[0, :, head // 2].T / np.sqrt(24)
        s = np.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ np.asarray(v)[0, :, head // 2]
        np.testing.assert_allclose(out[0, :, head], want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_both_routes_agree_to_the_bit_at_192_and_128(variant, monkeypatch):
    """The resident kernels and the streaming / split ones run the same steps
    on the same blocks at two widths too."""
    q, k, v, g, g_lse, window, plane = _operands(192, 128, variant, seed=1)

    def run():
        pa._make_flash.cache_clear()        # JAX keeps the trace of a custom rule
        return _results(lambda q, k, v: pa.flash_attention_lse(
            q, k, v, causal=True, window=window, keep=plane, block_q=32, block_k=32,
            interpret=True), q, k, v, g, g_lse)

    resident = run()
    assert pa.bwd_route(T, 192, jnp.float32, 32, 32, plane is not None, 128).route == "resident"
    monkeypatch.setattr(pa, "_vmem_bytes", lambda: 1 << 10)
    assert pa.fwd_route(T, 192, jnp.float32, 32, 32, plane is not None, 128).route == "streaming"
    other = run()
    pa._make_flash.cache_clear()
    # where both routes mask the same blocks the values are the same to the
    # bit; the causal call's whole blocks are masked by the streaming forward
    # and not by the resident one, and XLA:CPU then rounds a last bit apart
    # (at one width as at two, before PR 48 as after)
    exact = VARIANTS[variant][2] is not None or plane is not None
    for name in resident:
        if exact:
            np.testing.assert_array_equal(resident[name], other[name], err_msg=name)
        else:
            np.testing.assert_allclose(resident[name], other[name], rtol=1e-5, atol=2e-6,
                                       err_msg=name)


def test_the_plans_count_both_widths_and_say_so(bwd_log):  # noqa: F811
    vmem = 128 << 20
    fwd = pa._fwd_plan(4096, 192, "bfloat16", 1024, 1024, vmem, False, 128)
    bwd = pa._bwd_plan(4096, 192, "bfloat16", 1024, 512, vmem, False, 128)
    lines = list({id(r): r.getMessage() for r in bwd_log.records}.values())
    assert [line.count("head 192 | v 128") for line in lines] == [1, 1]
    # a width takes whole lane tiles: 192 is counted as 256, 64 as 128
    assert pa._in_vmem(192) == 256 and pa._in_vmem(128) == 128 and pa._in_vmem(64) == 128
    assert fwd.route == bwd.route == "resident"
    # narrower than a head of 256 everywhere, wider than one of 128
    wide, narrow = (pa._fwd_plan(4096, d, "bfloat16", 1024, 1024, vmem) for d in (256, 128))
    assert narrow.vmem_bytes < fwd.vmem_bytes < wide.vmem_bytes
    # one width given: the other is the same, plan for plan
    assert pa._bwd_plan(4096, 128, "bfloat16", 1024, 1024, vmem, False, 128) \
        == pa._bwd_plan(4096, 128, "bfloat16", 1024, 1024, vmem)
    # the backward's key block follows q's and k's width: the power of two
    # under 1024 x 128 / 192
    shape = lambda d: (1, 4096, 32, d)
    assert pa._plan_blocks(shape(192), shape(192), None, None, dtype=jnp.bfloat16) == (1024, 512)
    assert pa._plan_blocks(shape(192), shape(192), None, None, dtype=jnp.bfloat16,
                           forward=True) == (1024, 1024)


# ------------------------------------------------------------------ #
# at Dv == Dqk: what the parent traced, cell by cell

with open(os.path.join(os.path.dirname(__file__), "flash_signatures_pr46.json")) as f:
    PARENT = json.load(f)
# (batch, tokens, heads, key-value heads, head, window, a data mask): the six
# LM cells' attention calls as `test_pallas_attention_routes.py` lists them
CELLS = {cell: FORWARD_ROUTES[cell][0] for cell in PARENT}


def signature(cell, v_dim=None):
    """The call's forward and backward as they trace at a cell's shape on a
    described v5e (nothing runs): every `pallas_call`'s name, grid, block
    shapes and output shapes, the planned blocks and both plans."""
    b, t, heads, kv_heads, head, window, keep = CELLS[cell]
    dt = jnp.bfloat16
    shaped = lambda shape, d=dt: jax.ShapeDtypeStruct(shape, d)
    q, k, v = (b, t, heads, head), (b, t, kv_heads, head), (b, t, kv_heads, v_dim or head)

    def f(q, k, v, *plane):
        out, vjp = jax.vjp(lambda q, k, v: pa.flash_attention(
            q, k, v, window=window, keep=plane[0] if plane else None, interpret=False),
            q, k, v)
        return out, vjp(out)

    jaxpr = jax.make_jaxpr(f)(shaped(q), shaped(k), shaped(v),
                              *([shaped((b, t, t), jnp.int8)] if keep else [])).jaxpr
    calls = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                calls.append([eqn.params["name"], list(gm.grid),
                              [[str(d) for d in bm.block_shape] for bm in gm.block_mappings],
                              [list(map(int, s.shape)) for s in eqn.params["out_avals"]]])
            for value in eqn.params.values():
                if hasattr(value, "jaxpr"):
                    walk(value.jaxpr)
                elif hasattr(value, "eqns"):
                    walk(value)

    walk(jaxpr)
    fwd = pa._plan_blocks(q, k, None, None, dtype=dt, keep=keep, forward=True)
    bwd = pa._plan_blocks(q, k, None, None, dtype=dt, keep=keep)
    # (route, bytes, limit) as the parent recorded them; every one of these
    # shapes fits with the pipeline's two buffers a whole-head block (PR 63)
    plans = pa.fwd_route(t, head, dt, *fwd, keep=keep), pa.bwd_route(t, head, dt, *bwd, keep=keep)
    assert [plan.buffers for plan in plans] == [2, 2]
    return {"calls": calls, "fwd_blocks": list(fwd), "bwd_blocks": list(bwd),
            "fwd_plan": list(plans[0][:3]), "bwd_plan": list(plans[1][:3])}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_one_width_traces_what_the_parent_traced(cell, monkeypatch):
    monkeypatch.setattr(pa, "_vmem_bytes", lambda: 128 << 20)
    assert len(CELLS) == 7
    got = signature(cell)
    assert [c[0] for c in got["calls"]] == [c[0] for c in PARENT[cell]["calls"]]
    assert got == PARENT[cell]


def test_two_widths_change_the_blocks_that_are_v_s_and_no_other(monkeypatch):
    """GLM's shape with v of 128 beside q and k of 256: the same kernels and
    grids; v's, the output's, its cotangent's and dv's blocks 128 wide."""
    monkeypatch.setattr(pa, "_vmem_bytes", lambda: 128 << 20)
    one, two = signature("glm-4.7-flash.resident-8k"), signature("glm-4.7-flash.resident-8k", 128)
    assert [c[:2] for c in one["calls"]] == [c[:2] for c in two["calls"]]
    width = lambda call: [int(block[3].strip("Blocked()")) for block in call[2]]
    fwd, bwd = two["calls"]
    assert width(fwd) == [256, 256, 128, 128, 128]                  # q, k, v | out, lse
    # q, k, v, out, its cotangent, lse | dq, dk, dv
    assert width(bwd) == [256, 256, 128, 128, 128, 128, 256, 256, 128]
    assert fwd[3] == [[1, 20, 8192, 128], [1, 20, 8192, 128]]
    assert bwd[3] == [[1, 20, 8192, 256], [1, 20, 8192, 256], [1, 20, 8192, 128]]
    for route, blocks in ((pa.fwd_route, two["fwd_blocks"]), (pa.bwd_route, two["bwd_blocks"])):
        assert route(8192, 256, jnp.bfloat16, *blocks, v_dim=128).vmem_bytes \
            < route(8192, 256, jnp.bfloat16, *blocks).vmem_bytes
