"""GPipe pipeline parallelism (parallel/pipeline.py): forward and GRADIENT
parity with sequential stage folding on a virtual mesh, fallback without a
pp axis, and comm-structure bounds (activation-sized collectives only).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.parallel.mesh import build_mesh
from elasticdl_tpu.parallel.pipeline import gpipe, stage_partition_specs
from tests.conftest import heavy_on_cpu

S, DIN = 4, 8


def make_params(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "w": jnp.asarray(rng.randn(S, DIN, DIN) * 0.3, jnp.float32),
        "b": jnp.asarray(rng.randn(S, DIN) * 0.1, jnp.float32),
    }


def stage(p, a):
    return jax.nn.relu(a @ p["w"] + p["b"])


def sequential(params, x):
    for s in range(S):
        x = stage(jax.tree_util.tree_map(lambda l: l[s], params), x)
    return x


@pytest.mark.parametrize("mesh_axes", [
    {"pp": 4},
    pytest.param({"data": 2, "pp": 4}, marks=heavy_on_cpu),
])
@pytest.mark.usefixtures("mesh8")
@pytest.mark.parametrize("num_microbatches", [1, 2, 4])
def test_gpipe_matches_sequential_fwd_and_grad(mesh_axes, num_microbatches):
    params = make_params()
    x = jnp.asarray(np.random.RandomState(1).randn(8, DIN), jnp.float32)
    devices = jax.devices()[: int(np.prod(list(mesh_axes.values())))]
    mesh = build_mesh(mesh_axes, devices)
    with jax.set_mesh(mesh):
        ref = sequential(params, x)
        got = jax.jit(
            lambda p, x: gpipe(stage, p, x,
                               num_microbatches=num_microbatches)
        )(params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5)

        # pipelined BACKPROP: grad through the schedule equals sequential
        g_ref = jax.grad(lambda p: jnp.sum(sequential(p, x) ** 2))(params)
        g_got = jax.jit(jax.grad(
            lambda p: jnp.sum(
                gpipe(stage, p, x,
                      num_microbatches=num_microbatches) ** 2)
        ))(params)
        for k in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(g_got[k]), np.asarray(g_ref[k]),
                rtol=1e-4, atol=1e-6)


def test_gpipe_without_pp_axis_falls_back_sequential(mesh8):
    params = make_params()
    x = jnp.asarray(np.random.RandomState(2).randn(4, DIN), jnp.float32)
    with jax.set_mesh(mesh8):   # mesh has only a data axis
        got = jax.jit(
            lambda p, x: gpipe(stage, p, x, num_microbatches=2))(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(sequential(params, x)),
                               rtol=1e-5)


def test_gpipe_batch_divisibility_error():
    params = make_params()
    x = jnp.zeros((6, DIN), jnp.float32)
    mesh = build_mesh({"pp": 4}, jax.devices()[:4])
    with jax.set_mesh(mesh), pytest.raises(ValueError, match="divisible"):
        gpipe(stage, params, x, num_microbatches=4)


def test_gpipe_collectives_are_activation_sized():
    """The pipeline's collectives are the per-tick activation ppermute and
    the final output psum — nothing stage-param-sized ever crosses the
    ring (stage weights stay resident; that is the point of pp)."""
    from tests.test_comm_structure import collective_sizes

    params = make_params()
    x = jnp.asarray(np.random.RandomState(3).randn(8, DIN), jnp.float32)
    mesh = build_mesh({"pp": 4}, jax.devices()[:4])
    param_elems = S * DIN * DIN
    mb_elems = 2 * DIN              # (mb=2, DIN) activation
    out_elems = 4 * 2 * DIN         # stacked (M, mb, DIN) output psum
    with jax.set_mesh(mesh):
        hlo = (
            jax.jit(jax.grad(
                lambda p: jnp.sum(
                    gpipe(stage, p, x, num_microbatches=4) ** 2)))
            .lower(params).compile().as_text()
        )
    sizes = collective_sizes(hlo)
    assert sizes, "expected ppermute/psum collectives in the pipeline HLO"
    for op, n in sizes:
        assert n <= out_elems, (op, n, "param-sized collective leaked")
        assert n < param_elems, (op, n)


def test_stage_partition_specs():
    from jax.sharding import PartitionSpec as P

    specs = stage_partition_specs(make_params())
    assert specs["w"] == P("pp", None, None)
    assert specs["b"] == P("pp", None)


def test_gpipe_stage_count_mismatch_error():
    params = make_params()   # S=4 stages
    x = jnp.zeros((8, DIN), jnp.float32)
    mesh = build_mesh({"pp": 2}, jax.devices()[:2])
    with jax.set_mesh(mesh), pytest.raises(ValueError, match="must match"):
        gpipe(stage, params, x, num_microbatches=4)
