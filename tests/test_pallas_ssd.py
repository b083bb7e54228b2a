"""The state-space scan's kernels (`ops/pallas_ssd.py`, interpret mode on the
CPU) against `ops/ssm.py`'s plain body AND against the token-by-token
recurrence of the benchmark's reference: y and the gradients of x, Δ, A, B
and C, in float32 and with bfloat16 operands, at a length that is whole
chunks and one that leaves a tail, two sequences, two groups of two heads,
decays slow enough that a state crosses every chunk of the sequence."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from elasticdl_tpu.ops import pallas_ssd, ssm
from elasticdl_tpu.ops.pallas_attention import interpret_mode

reference = common.load_module("reference", "nemotron_h")

CHUNK = 128
QUANTITIES = ("y", "dx", "ddelta", "da", "db", "dc")
CASES = [("float32", 512), ("float32", 300), ("bfloat16", 512), ("bfloat16", 300)]


def scan_inputs(tokens, heads=4, head_dim=64, groups=2, state=128, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(2, tokens, heads, head_dim))
    # Δ·A of a few thousandths a token: a chunk keeps over half of the state
    delta = 0.02 * np.log1p(np.exp(r.normal(size=(2, tokens, heads))))
    a = -np.exp(0.3 * r.normal(size=(heads,)))
    b, c = (r.normal(size=(2, tokens, groups, state)) for _ in range(2))
    return [np.asarray(v, np.float32) for v in (x, delta, a, b, c)]


def token_by_token(x, delta, a, b, c):
    heads = x.shape[2] // b.shape[2]
    return reference.recurrence(x, delta, a, jnp.repeat(b, heads, axis=2),
                                jnp.repeat(c, heads, axis=2))


@functools.lru_cache(maxsize=None)
def results(dtype, tokens):
    """{route: {quantity: array}} for the kernels, the plain body (both with
    `dtype` operands) and the float32 recurrence."""
    args = scan_inputs(tokens)
    probe = jnp.asarray(np.random.default_rng(9).normal(size=args[0].shape), jnp.float32)
    chunked = lambda *v: ssm.ssd_chunked(*v, CHUNK, jnp.dtype(dtype))

    def of(scan):
        _, grads = jax.value_and_grad(
            lambda *v: jnp.sum(probe * scan(*v)), argnums=(0, 1, 2, 3, 4))(*args)
        return dict(zip(QUANTITIES, (np.asarray(scan(*args)),) + tuple(map(np.asarray, grads))))

    with jax.default_matmul_precision("highest"):
        out = {"plain": of(chunked), "recurrence": of(token_by_token)}
        assert "pallas_call" not in str(jax.make_jaxpr(lambda *v: chunked(*v))(*args))
        with interpret_mode():
            assert "ssd_chunk_fwd" in str(jax.make_jaxpr(lambda *v: chunked(*v))(*args))
            out["kernel"] = of(chunked)
    return out


def apart(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("quantity", QUANTITIES)
@pytest.mark.parametrize("dtype,tokens", CASES)
def test_kernels_match_the_plain_body(dtype, tokens, quantity):
    """Same rounding points forward, so with bfloat16 operands y differs only
    where a sum in another order rounds an operand the other way (a few
    elements, 1e-5 in all); the plain body's autodiff rounds the cotangents
    of bfloat16 operands to bfloat16, the kernel keeps them float32."""
    r = results(dtype, tokens)
    limit = 2e-6 if dtype == "float32" else 1e-4 if quantity == "y" else 1e-2
    assert apart(r["kernel"][quantity], r["plain"][quantity]) < limit


@pytest.mark.parametrize("quantity", QUANTITIES)
@pytest.mark.parametrize("dtype,tokens", CASES)
def test_kernels_match_the_recurrence(dtype, tokens, quantity):
    r = results(dtype, tokens)
    got = apart(r["kernel"][quantity], r["recurrence"][quantity])
    if dtype == "float32":
        assert got < 2e-5
    else:
        # about as far from the recurrence as the plain body is (da is four numbers)
        assert got < 2e-2
        assert got < 1.5 * apart(r["plain"][quantity], r["recurrence"][quantity]) + 1e-3


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_state_crosses_three_chunks(dtype):
    """What the LAST chunk's outputs owe to the FIRST chunk's inputs passes
    through the carried state alone, over two whole chunks between."""
    args = scan_inputs(512)
    first, last = slice(0, CHUNK), slice(3 * CHUNK, 4 * CHUNK)

    def owed(scan):
        return np.asarray(jax.grad(lambda x: jnp.sum(scan(x, *args[1:])[:, last]))(
            jnp.asarray(args[0]))[:, first])

    with jax.default_matmul_precision("highest"):
        want = owed(token_by_token)
        with interpret_mode():
            got = owed(lambda *v: ssm.ssd_chunked(*v, CHUNK, jnp.dtype(dtype)))
    assert np.linalg.norm(want) > 1e-2 * np.linalg.norm(want[:, -1:]) > 0
    assert apart(got, want) < (2e-5 if dtype == "float32" else 2e-2)


def test_a_head_as_wide_as_a_lane_tile():
    """One head a lane tile (no mask) beside the two-a-tile cases above."""
    args = scan_inputs(256, heads=2, head_dim=128, groups=1)
    with jax.default_matmul_precision("highest"):
        want = token_by_token(*args)
        with interpret_mode():
            got = ssm.ssd_chunked(*args, CHUNK, jnp.float32)
    assert apart(np.asarray(got), np.asarray(want)) < 2e-5


ACCEPTED = ((1, 8192, 64, 64), (1, 8192, 8, 128), 128)       # the benchmark's cell
REFUSED = {
    "chunk_not_whole_lanes": ((1, 64, 4, 64), (1, 64, 2, 128), 8),
    "state_not_whole_lanes": ((1, 256, 4, 64), (1, 256, 2, 16), 128),
    "heads_not_whole_lane_tiles": ((1, 256, 6, 64), (1, 256, 2, 128), 128),
    "head_no_part_of_a_lane_tile": ((1, 256, 4, 48), (1, 256, 2, 128), 128),
    "visit_larger_than_vmem": ((1, 256, 512, 128), (1, 256, 1, 1024), 128),
}


@pytest.mark.parametrize("runnable", [True, False])
def test_the_cell_s_shape_takes_the_kernels_where_they_can_run(runnable, route_log):
    route = ssm.scan_route(*ACCEPTED, runnable)
    assert route == ("kernel" if runnable else "plain")
    assert f"takes the {route} route" in route_log.text and "64 heads of 64" in route_log.text


@pytest.mark.parametrize("why", sorted(REFUSED))
def test_a_shape_the_rule_refuses_takes_the_plain_body(why, route_log):
    x_shape, bc_shape, chunk = REFUSED[why]
    assert pallas_ssd.blocks(x_shape[2], x_shape[3], bc_shape[2], bc_shape[3], chunk) is None
    assert ssm.scan_route(x_shape, bc_shape, chunk, True) == "plain"
    assert "takes the plain route" in route_log.text and "inside VMEM: no" in route_log.text


def test_the_cell_s_blocks_fit_the_vmem_the_rule_allows():
    plan = pallas_ssd.blocks(64, 64, 8, 128, 128)
    assert (plan.lane_tile, plan.heads_a_tile) == (128, 2)
    assert plan.vmem_bytes <= pallas_ssd._vmem_bytes() // 2 < plan.vmem_limit


def test_off_the_tpu_and_outside_interpret_mode_the_scan_is_the_plain_body():
    args = scan_inputs(256)
    assert not pallas_ssd.runnable()
    text = jax.jit(lambda *v: ssm.ssd_chunked(*v, CHUNK, jnp.float32)).lower(*args).as_text()
    assert "ssd_chunk" not in text
