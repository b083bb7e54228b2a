"""Serving export round-trip: trained (sharded) state → export dir → reload →
identical forward outputs on a single device. Mirrors the reference's
model_handler tests (reference: elasticdl/python/tests/model_handler_test.py),
where Embedding→keras export had to reproduce the PS table contents exactly.
"""

import numpy as np
import pytest


from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.training.export import (
    export_model,
    load_model,
    load_variables,
    read_info,
)
from elasticdl_tpu.training.model_spec import ModelSpec
from elasticdl_tpu.training.trainer import Trainer
from elasticdl_tpu.worker.prediction_outputs_processor import (
    InMemoryPredictionOutputsProcessor,
    NpyPredictionOutputsProcessor,
)
from tests.conftest import heavy_on_cpu

MODEL_PARAMS = {"field_vocab": 64, "hidden": "32,32"}


def deepfm_batch(n=16, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "features": {
            "dense": rng.rand(n, 13).astype(np.float32),
            "cat": rng.randint(0, 1 << 30, size=(n, 26)).astype(np.int32),
        },
        "labels": rng.randint(0, 2, size=(n,)).astype(np.int32),
        "mask": np.ones((n,), np.float32),
    }


@pytest.fixture(scope="module")
def trained(mesh_4x2):
    cfg = JobConfig(
        model_zoo="model_zoo",
        model_def="deepfm.deepfm.custom_model",
        model_params=MODEL_PARAMS,
    )
    spec = ModelSpec.from_config(cfg)
    trainer = Trainer(spec, mesh_4x2, seed=0)
    state = trainer.init_state(deepfm_batch())
    for i in range(3):
        state, _ = trainer.train_step(state, deepfm_batch(seed=i))
    return spec, trainer, state


def test_export_roundtrip_forward_parity(trained, tmp_path):
    spec, trainer, state = trained
    out = str(tmp_path / "export")
    export_model(
        state, out, model_def="deepfm.deepfm.custom_model",
        model_params=MODEL_PARAMS, module_name=spec.module_name,
    )

    info = read_info(out)
    assert info["model_def"] == "deepfm.deepfm.custom_model"
    assert info["step"] == 3
    assert info["num_params"] > 0

    batch = deepfm_batch(seed=9)
    expected = np.asarray(trainer.predict_step(state, batch))

    model, variables = load_model(out, "model_zoo")
    got = np.asarray(model.apply(variables, batch["features"], training=False))
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5)


def test_exported_table_matches_sharded_state(trained, tmp_path):
    """The sharded embedding table must re-assemble exactly (the reference's
    export bug class: PS shard iteration order scrambling rows)."""
    import jax
    import flax.linen as nn

    spec, _, state = trained
    out = str(tmp_path / "export")
    export_model(state, out, model_def="deepfm.deepfm.custom_model")
    tree = load_variables(out)

    flat_state = {
        "/".join(map(str, k)): v
        for k, v in jax.tree_util.tree_leaves_with_path(
            nn.meta.unbox(state.params)
        )
    }
    flat_export = {
        "/".join(map(str, k)): v
        for k, v in jax.tree_util.tree_leaves_with_path(tree["params"])
    }
    assert flat_state.keys() == flat_export.keys()
    table_keys = [k for k in flat_state if "embedding" in k.lower()]
    assert table_keys, list(flat_state)
    for k in flat_state:
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(flat_state[k])), flat_export[k]
        )


def test_prediction_outputs_processors(tmp_path):
    mem = InMemoryPredictionOutputsProcessor()
    mem.process(np.arange(6).reshape(3, 2), worker_id=0)
    mem.process(np.arange(4).reshape(2, 2), worker_id=0)
    assert mem.result().shape == (5, 2)

    npy = NpyPredictionOutputsProcessor(str(tmp_path / "preds"))
    npy.process(np.ones((4, 2), np.float32), worker_id=1)
    npy.process(np.zeros((2, 2), np.float32), worker_id=1)
    npy.close()
    import glob

    files = sorted(glob.glob(str(tmp_path / "preds" / "*.npy")))
    assert len(files) == 2
    assert np.load(files[0]).shape == (4, 2)


def test_saved_model_export(trained, tmp_path):
    """jax2tf serving artifact matches the reference's output format
    (reference: model_handler exports a TF SavedModel)."""
    tf = pytest.importorskip("tensorflow")
    from elasticdl_tpu.training.export import export_saved_model

    spec, trainer, state = trained
    out = str(tmp_path / "export")
    export_model(
        state, out, model_def="deepfm.deepfm.custom_model",
        model_params=MODEL_PARAMS,
    )
    batch = deepfm_batch(seed=11)
    path = export_saved_model(out, "model_zoo", batch["features"])
    if path is None:
        pytest.skip("jax2tf/TF unavailable")
    served = tf.saved_model.load(path)
    got = np.asarray(served.serve(batch["features"]))
    expected = np.asarray(trainer.predict_step(state, batch))
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-4)
    # the signature must not bake in the export-time batch size
    small = {k: v[:3] for k, v in batch["features"].items()}
    assert np.asarray(served.serve(small)).shape == (3,)


@pytest.mark.parametrize("params", [
    {"tp_axis": "model"},
    pytest.param({"pp_axis": "pp", "num_layers": 4}, marks=heavy_on_cpu),
])
def test_export_roundtrip_tp_and_pp_lm(params, tmp_path):
    """Serving completeness for the parallel LM variants: a TP- or
    PP-sharded trained state exports (shards gathered to host) and
    reloads on a plain data-only mesh with identical forward outputs —
    the partitioned/stacked layouts are a training-time concern only."""
    import jax

    from elasticdl_tpu.parallel.mesh import build_mesh

    lm_params = {
        "vocab": 64, "num_layers": 2, "dim": 32, "heads": 4,
        "max_len": 32, "seq_parallel": "none", "compute_dtype": "float32",
        **params,
    }
    cfg = JobConfig(
        model_zoo="model_zoo",
        model_def="transformer.transformer_lm.custom_model",
        model_params=lm_params,
    )
    spec = ModelSpec.from_config(cfg)
    mesh = build_mesh(
        {"data": 2, "model": 4} if "tp_axis" in params
        else {"data": 2, "pp": 4})
    trainer = Trainer(spec, mesh, seed=0)

    rng = np.random.RandomState(0)
    batch = {
        "features": rng.randint(0, 64, (4, 16)).astype(np.int32),
        "labels": rng.randint(0, 64, (4, 16)).astype(np.int32),
        "mask": np.ones((4,), np.float32),
    }
    state = trainer.init_state(batch)
    state, _ = trainer.train_step(state, batch)

    out = str(tmp_path / "export")
    export_model(
        state, out, model_def="transformer.transformer_lm.custom_model",
        model_params=lm_params, module_name=spec.module_name,
    )
    expected = np.asarray(
        jax.device_get(trainer.predict_step(state, batch)))

    # reload on a 2-device data-only mesh: no model/pp axis anywhere
    serve_mesh = build_mesh({"data": 2}, jax.devices()[:2])
    with jax.set_mesh(serve_mesh):
        model, variables = load_model(out, "model_zoo")
        got = np.asarray(model.apply(
            variables, batch["features"], training=False))
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-5)
