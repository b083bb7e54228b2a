"""The CIN kernels (`ops/pallas_cin.py`) against the einsum route of
`model_zoo/deepfm/xdeepfm.py`, in interpret mode on the CPU: a layer's y, dxk,
dx0 and dW at the benchmark's widths and a small one, over columns a tile
divides and columns it does not; dW against float64 NumPy; the parameter's
(h, f) <-> f-major permutation; `cin_route`; the model's parameter tree and
one set of weights through both routes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from elasticdl_tpu.ops import pallas_cin
from elasticdl_tpu.ops.pallas_attention import interpret_mode
from model_zoo.deepfm import xdeepfm

# name: (H, O, F, D, B): 256 examples are two tiles of 128; 13 and 9 are
# padded to one with zero columns
WIDTHS = {
    "first_layer": (26, 200, 26, 10),
    "inner_layer": (200, 200, 26, 10),
    "small": (16, 16, 26, 16),
}
CASES = {
    f"{name}-{cols}-{jnp.dtype(dtype).name}": (*widths, batch, dtype)
    for name, widths in WIDTHS.items()
    for cols, batch in (("tile_divides", 256), ("zero_padded", 13))
    for dtype in (jnp.bfloat16, jnp.float32)
}
COLS = 128


def operands(h, o, f, d, b, dtype, seed=0):
    r = np.random.default_rng(seed)
    w = jnp.asarray(r.normal(size=(o, h * f)) / np.sqrt(h * f), jnp.float32)
    xk = jnp.asarray(r.normal(size=(b, h, d)), dtype)
    x0 = jnp.asarray(r.normal(size=(b, f, d)), dtype)
    g = jnp.asarray(r.normal(size=(b, o, d)), dtype)
    gs = jnp.asarray(r.normal(size=(b, o)), dtype)
    return w, xk, x0, g, gs


def einsum_layer(w, xk, x0):
    wr = w.astype(xk.dtype).reshape(w.shape[0], xk.shape[1], x0.shape[1])
    y = jnp.einsum("ohf,bhd,bfd->bod", wr, xk, x0)
    return y, jnp.sum(y, axis=-1)


def from_columns(xc, b, r, d):
    """`to_columns` back: (Rp, D * Bp) -> (B, R, D)."""
    return xc.reshape(xc.shape[0], d, -1)[:r, :, :b].transpose(2, 0, 1)


def w_from_rows(rows, h, o, f):
    """`w_rows` back: (F * Hp, Op) -> (O, H * F) in (h, f) order."""
    hp = rows.shape[0] // f
    return rows.reshape(f, hp, -1)[:, :h, :o].transpose(2, 1, 0).reshape(o, h * f)


def kernel_layer(w, xk, x0, g, gs):
    """One layer through the two kernels, in and out of their layout: y, its
    sum over d, and dxk, dx0, dW from the cotangents g of y and gs of the sum."""
    (b, h, d), f, o = xk.shape, x0.shape[1], w.shape[0]
    wt = pallas_cin.w_rows(w, h, f).astype(xk.dtype)
    xkc, x0c = pallas_cin.to_columns(xk, COLS), pallas_cin.to_columns(x0, COLS)
    y, s = pallas_cin._cin_fwd(wt, xkc, x0c, d=d, cols=COLS, interpret=True)
    dxk, dx0, dwt = pallas_cin._cin_bwd(
        wt, xkc, x0c, pallas_cin.to_columns(gs[:, :, None], COLS),
        pallas_cin.to_columns(g, COLS), d=d, cols=COLS, interpret=True)
    return ((from_columns(y, b, o, d), from_columns(s, b, o, 1)[..., 0]),
            (w_from_rows(dwt, h, o, f), from_columns(dxk, b, h, d),
             from_columns(dx0, b, f, d)))


def close(got, want, dtype):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # bfloat16: the two routes round the plane and the sums in another order
    tol = 2e-2 if jnp.dtype(dtype) == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * np.abs(want).max())


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_layer_matches_the_einsum_in_y_dxk_dx0_and_dw(name):
    h, o, f, d, b, dtype = CASES[name]
    w, xk, x0, g, gs = operands(h, o, f, d, b, dtype)
    want, pull = jax.vjp(einsum_layer, w, xk, x0)
    with interpret_mode():
        got, grads = kernel_layer(w, xk, x0, g, gs)
    for mine, theirs in zip(got + grads, want + pull((g, gs))):
        assert mine.shape == theirs.shape
        close(mine, theirs, dtype)


def test_the_padded_rows_and_columns_come_back_zero():
    h, o, f, d, b = 26, 24, 26, 10, 13
    w, xk, x0, g, gs = operands(h, o, f, d, b, jnp.float32, seed=5)
    wt = pallas_cin.w_rows(w, h, f)
    xkc, x0c = pallas_cin.to_columns(xk, COLS), pallas_cin.to_columns(x0, COLS)
    with interpret_mode():
        y, s = pallas_cin._cin_fwd(wt, xkc, x0c, d=d, cols=COLS, interpret=True)
        dxk, dx0, dwt = pallas_cin._cin_bwd(
            wt, xkc, x0c, pallas_cin.to_columns(gs[:, :, None], COLS), y,
            d=d, cols=COLS, interpret=True)
    cube = lambda a: np.asarray(a).reshape(a.shape[0], -1, COLS)
    assert y.shape == (32, d * COLS) and s.shape == (32, COLS)
    assert np.asarray(y).any() and np.asarray(dwt).any()
    for a, rows in ((y, o), (s, o), (dxk, h), (dx0, f)):
        assert not cube(a)[rows:].any() and not cube(a)[:, :, b:].any()
    assert not np.asarray(dwt).reshape(f, 32, 32)[:, h:].any()
    assert not np.asarray(dwt)[:, o:].any()


def test_the_last_layer_takes_no_cotangent_of_y():
    h, o, f, d, b = 16, 16, 26, 16, 9
    w, xk, x0, g, gs = operands(h, o, f, d, b, jnp.float32, seed=2)
    wt = pallas_cin.w_rows(w, h, f)
    args = (wt, pallas_cin.to_columns(xk, COLS), pallas_cin.to_columns(x0, COLS),
            pallas_cin.to_columns(gs[:, :, None], COLS))
    with interpret_mode():
        without = pallas_cin._cin_bwd(*args, d=d, cols=COLS, interpret=True)
        zero = pallas_cin._cin_bwd(*args, jnp.zeros((16, d * COLS), jnp.float32),
                                   d=d, cols=COLS, interpret=True)
    for a, b_ in zip(without, zero):
        np.testing.assert_array_equal(a, b_)


def test_dw_matches_a_float64_einsum():
    h, o, f, d, b = 16, 16, 26, 16, 16
    w, xk, x0, g, gs = operands(h, o, f, d, b, jnp.float32, seed=3)
    with interpret_mode():
        dw = kernel_layer(w, xk, x0, g, gs)[1][0]
    want = np.einsum("bod,bhd,bfd->ohf", *(np.asarray(a, np.float64) for a in (
        g + gs[:, :, None], xk, x0)))
    np.testing.assert_allclose(dw, want.reshape(o, h * f), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("h, o, f", [(26, 200, 26), (200, 200, 26), (16, 24, 5)])
def test_the_f_major_permutation_of_w_and_of_dw_round_trips(h, o, f):
    r = np.random.default_rng(h)
    w = jnp.asarray(r.normal(size=(o, h * f)), jnp.float32)
    rows, pull = jax.vjp(lambda a: pallas_cin.w_rows(a, h, f), w)
    hp, op = -(-h // 16) * 16, -(-o // 16) * 16
    assert rows.shape == (f * hp, op)
    # row f * Hp + h, column o is W[o, h * F + f]; the padding is zero
    cube = np.asarray(rows).reshape(f, hp, op)
    np.testing.assert_array_equal(cube[:, :h, :o],
                                  np.asarray(w).reshape(o, h, f).transpose(2, 1, 0))
    assert not cube[:, h:].any() and not cube[:, :, o:].any()
    np.testing.assert_array_equal(w_from_rows(rows, h, o, f), w)
    # a gradient in the kernels' order comes back in the parameter's
    d_rows = jnp.asarray(r.normal(size=rows.shape), jnp.float32)
    np.testing.assert_array_equal(pull(d_rows)[0],
                                  w_from_rows(d_rows, h, o, f))


BENCH = ((55296, 26, 10), (200, 200, 200))


@pytest.mark.parametrize("runnable, devices, dtype, route", [
    (True, 1, jnp.bfloat16, "kernel"),       # a TPU or interpret mode, no mesh
    (True, 1, jnp.float32, "kernel"),
    (False, 1, jnp.bfloat16, "einsum"),      # the CPU, plain
    (True, 2, jnp.bfloat16, "einsum"),       # a mesh of 2
    (True, 1, jnp.float16, "einsum"),        # a dtype the kernels do not take
])
def test_cin_route_from_the_backend_the_mesh_and_the_dtype(runnable, devices, dtype, route):
    assert pallas_cin.cin_route(*BENCH, dtype, runnable, devices) == route


def test_cin_route_as_the_model_calls_it():
    args = (*BENCH, jnp.bfloat16)
    assert not pallas_cin.runnable() and pallas_cin.ambient_devices() == 1
    with interpret_mode():
        assert pallas_cin.runnable()
        assert pallas_cin.cin_route(*args, pallas_cin.runnable(),
                                    pallas_cin.ambient_devices()) == "kernel"
        with jax.set_mesh(Mesh(np.array(jax.devices()[:2]), ("data",))):
            assert pallas_cin.ambient_devices() == 2
            assert pallas_cin.cin_route(*args, pallas_cin.runnable(),
                                        pallas_cin.ambient_devices()) == "einsum"
        with jax.set_mesh(Mesh(np.array(jax.devices()[:1]), ("data",))):
            assert pallas_cin.ambient_devices() == 1


def test_tiles_at_the_benchmarks_shapes_divide_its_batch():
    for h in (26, 200):
        cols = pallas_cin.column_tile(h, 200, 26, 55296, jnp.bfloat16)
        assert cols == 512 and 55296 % cols == 0
        assert pallas_cin.chunk_fields(h, 200, 26, cols, jnp.bfloat16) == 26
    assert pallas_cin.network_tiles(*BENCH, jnp.bfloat16) == 512
    # 13 examples are one tile of 128; a batch a 32nd over a tile's multiple
    # takes a narrower tile, not 511 columns of padding
    assert pallas_cin.column_tile(26, 16, 26, 13, jnp.float32) == 128
    assert pallas_cin.column_tile(26, 16, 26, 512 + 16, jnp.float32) == 128


def test_a_chunk_of_the_plane_follows_the_vmem_there_is(monkeypatch):
    args = (200, 200, 26, 512, jnp.bfloat16)
    for vmem, fields in ((128 << 20, 26), (64 << 20, 13), (48 << 20, 2), (16 << 20, 0)):
        monkeypatch.setattr(pallas_cin, "_vmem_bytes", lambda vmem=vmem: vmem)
        assert pallas_cin.chunk_fields(*args) == fields
    # no room for one field's rows: no tile, and the einsum is the route
    assert pallas_cin.network_tiles(*BENCH, jnp.bfloat16) == 0
    assert pallas_cin.cin_route(*BENCH, jnp.bfloat16, True, 1) == "einsum"


def test_the_whole_network_matches_the_einsum_route():
    b, f, d, sizes = 24, 26, 10, (24, 16, 40)
    r = np.random.default_rng(7)
    x0 = jnp.asarray(r.normal(size=(b, f, d)), jnp.float32)
    ws, h = [], f
    for o in sizes:
        ws.append(jnp.asarray(r.normal(size=(o, h * f)) / np.sqrt(h * f), jnp.float32))
        h = o
    ct = jnp.asarray(r.normal(size=(b, sum(sizes))), jnp.float32)
    want, pull = jax.vjp(xdeepfm.cin_einsum, ws, x0)
    with interpret_mode():
        got, pull_k = jax.vjp(pallas_cin.cin, ws, x0)
        grads = pull_k(ct)
    close(got, want, jnp.float32)
    for mine, theirs in zip(jax.tree_util.tree_leaves(grads),
                            jax.tree_util.tree_leaves(pull(ct))):
        close(mine, theirs, jnp.float32)


def _model_and_batch(batch=24):
    model = xdeepfm.custom_model(
        field_vocab=50, embedding_dim=10, hidden="16,16", cin_sizes="24,16,8")
    r = np.random.default_rng(11)
    feats = {"dense": jnp.asarray(r.random(size=(batch, 13)) * 5, jnp.float32),
             "cat": jnp.asarray(r.integers(0, 1 << 30, size=(batch, 26)), jnp.int32)}
    return model, feats


def test_the_parameter_tree_is_what_it_was():
    model, feats = _model_and_batch()
    shapes = {}
    for route in ("einsum", "kernel"):
        if route == "kernel":
            with interpret_mode():
                variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), feats)
        else:
            variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), feats)
        cin = variables["params"]["CIN_0"]
        shapes[route] = {k: (v.shape, v.dtype) for k, v in cin.items()}
    # (O, H * F) float32 under CIN_0/w{i}: checkpoints, the benchmark's
    # reference and its operation count read it so
    assert shapes["einsum"] == shapes["kernel"] == {
        "w0": ((24, 26 * 26), jnp.float32),
        "w1": ((16, 24 * 26), jnp.float32),
        "w2": ((8, 16 * 26), jnp.float32),
    }


def test_one_checkpoints_weights_give_the_same_logits_on_both_routes():
    model, feats = _model_and_batch()
    variables = model.init(jax.random.PRNGKey(0), feats)       # the einsum route
    want = model.apply(variables, feats)
    with interpret_mode():
        got = model.apply(variables, feats)                    # the kernels
    assert np.abs(np.asarray(want)).max() > 1e-3
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
