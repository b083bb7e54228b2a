"""The plain references against the zoo's models, tiny, on the CPU, with the
tower in float32 so that the comparison is of the mathematics alone."""

import numpy as np
import pytest

from benchmark import check, common, criteo_skew


@pytest.mark.parametrize("config_name", ["deepfm-criteo", "xdeepfm-criteo"])
def test_reference_matches_the_zoo_model(config_name):
    import jax

    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    config = common.load_json("configs", config_name + ".json")
    params = common.model_params(config)
    params.update(field_vocab="64", compute_dtype="float32")
    config["model_params"] = common.format_model_params(params)
    resident = common.load_module("drivers", "resident")
    _, spec, mesh, trainer = resident.build_trainer(config, jax.devices()[:1], seed=5)
    cards = common.load_json("cardinalities", "criteo-kaggle.json")["fields"]
    records = criteo_skew.generate(5, 4 * 128, cards)
    batches = resident._batches(records, 128, 0, 4)
    state = trainer.init_state(batches[0])
    reference = common.load_module("reference", common.model_name(config))
    checker = check.StepCheck(reference, params, batches, seed=5, micro_batch=64)
    checker.before(state)
    with jax.default_matmul_precision("highest"):
        state, m = trainer.train_many(
            state, shard_batch_stack(mesh, batches, spec.batch_partition))
    verdict = checker.after(state, np.asarray(m["loss"]))
    f = verdict["figures"]
    assert f["loss_rel"] < 1e-5, f
    assert f["mu_lin_rel_l2"] < 1e-4 and f["mu_emb_rel_l2"] < 1e-4, f
    assert f["mu_lin_rel_median"] < 1e-4, f
    assert not any("untouched" in x or "finite" in x for x in verdict["failures"])


def test_hash_is_the_programs():
    from elasticdl_tpu.api import preprocessing as pp

    reference = common.load_module("reference", "deepfm")
    raw = np.random.default_rng(0).integers(-2**31, 2**31 - 1, 4096).astype(np.int32)
    assert np.array_equal(reference.hash_bucket(raw, 1300000),
                          np.asarray(pp.hash_bucket(raw, 1300000)))
