"""Rescale fast path: executable cache keying (hit/miss counters),
speculative neighbor-world compilation, live state handoff vs the
checkpoint-restore round trip, and the worker's in-place rescale."""

import os

import numpy as np
import pytest

from elasticdl_tpu.common import membership_signal
from elasticdl_tpu.training import compile_cache as cc


def make_spec():
    from elasticdl_tpu.common.model_utils import load_module
    from elasticdl_tpu.training.model_spec import ModelSpec

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    module, _ = load_module(
        os.path.join(repo, "model_zoo"), "census.wide_deep.custom_model"
    )
    return ModelSpec(
        model=module.custom_model(),
        loss=module.loss,
        optimizer=module.optimizer(),
        dataset_fn=None,
        eval_metrics_fn=getattr(module, "eval_metrics_fn", None),
        module_name="census.wide_deep",
    )


def census_batch(n=16, seed=0):
    r = np.random.RandomState(seed)
    return {
        "features": {
            "dense": r.rand(n, 5).astype(np.float32),
            "cat": r.randint(0, 400, (n, 9)).astype(np.int32),
        },
        "labels": r.randint(0, 2, (n,)).astype(np.int32),
    }


@pytest.fixture(scope="module")
def spec():
    return make_spec()


def make_trainer(spec, mesh, cache, token="t"):
    from elasticdl_tpu.training.trainer import Trainer

    return Trainer(spec, mesh, cache_token=token, cache=cache)


# ------------------------------------------------------------------ #
# cache keying


def test_same_aval_rejit_is_cache_hit(spec, mesh8):
    """A second trainer on the same (mesh, token, knobs) finds the first
    trainer's programs: zero misses, counter-asserted."""
    cache = cc.CompileCache()
    batch = census_batch()
    t1 = make_trainer(spec, mesh8, cache)
    state = t1.init_state(batch)
    state, _ = t1.train_step(state, batch)
    first = cache.stats()
    assert first["misses"] == 2 and first["hits"] == 0  # init + train_step

    t2 = make_trainer(spec, mesh8, cache)
    state2 = t2.init_state(batch)
    state2, _ = t2.train_step(state2, batch)
    second = cache.stats()
    assert second["misses"] == 2, second   # nothing rebuilt
    assert second["hits"] == 2, second     # init + train_step both hits
    assert second["hit_rate"] == 0.5


def test_different_mesh_is_cache_miss(spec, mesh8):
    import jax

    from elasticdl_tpu.parallel.mesh import build_mesh

    cache = cc.CompileCache()
    batch = census_batch()
    t1 = make_trainer(spec, mesh8, cache)
    s1 = t1.init_state(batch)
    t1.train_step(s1, batch)
    before = cache.stats()

    mesh4 = build_mesh({"data": 4}, jax.devices()[:4])
    t2 = make_trainer(spec, mesh4, cache)
    s2 = t2.init_state(batch)
    t2.train_step(s2, batch)
    after = cache.stats()
    assert after["misses"] == before["misses"] + 2   # new mesh = new programs
    assert after["hits"] == before["hits"]


def test_instance_token_trainers_do_not_share(spec, mesh8):
    """No cache_token (ad-hoc trainers): entries are private — two
    trainers over the same spec still build their own programs."""
    cache = cc.CompileCache()
    batch = census_batch()
    from elasticdl_tpu.training.trainer import Trainer

    for _ in range(2):
        t = Trainer(spec, mesh8, cache=cache)
        s = t.init_state(batch)
        t.train_step(s, batch)
    stats = cache.stats()
    assert stats["hits"] == 0 and stats["misses"] == 4


def test_lru_eviction_bounds_entries():
    cache = cc.CompileCache(max_entries=2)
    for i in range(5):
        cache.get_or_build(("k", i), lambda i=i: i)
    assert cache.stats()["entries"] == 2
    # evicted key rebuilds (a miss), resident key hits
    assert cache.get_or_build(("k", 0), lambda: "rebuilt") == "rebuilt"
    assert cache.get_or_build(("k", 4), lambda: "wrong") == 4


# ------------------------------------------------------------------ #
# speculative neighbor compilation


def test_neighbor_world_sizes_with_simulated_cohort(monkeypatch):
    """Candidate ordering from a simulated multi-process context
    (EDL_NUM_PROCESSES): the announced pending size first, then N±1."""
    from elasticdl_tpu.parallel.elastic import (
        context_from_env, neighbor_world_sizes,
    )

    monkeypatch.setenv("EDL_NUM_PROCESSES", "4")
    monkeypatch.setenv("EDL_PROCESS_ID", "0")
    from elasticdl_tpu.common.config import JobConfig

    ctx = context_from_env(JobConfig(model_def="x"))
    assert ctx is not None and ctx.num_processes == 4
    assert neighbor_world_sizes(ctx.num_processes) == [3, 5]
    assert neighbor_world_sizes(ctx.num_processes, pending=2) == [2, 3, 5]
    assert neighbor_world_sizes(2, pending=2, min_size=1) == [1, 3]
    assert neighbor_world_sizes(1) == [2]


def test_membership_signal_roundtrip(tmp_path):
    path = str(tmp_path / "sig.json")
    assert membership_signal.pending_size(path) is None
    assert membership_signal.write_signal(path, world_size=4, pending_size=3)
    assert membership_signal.pending_size(path) == 3
    sig = membership_signal.read_signal(path)
    assert sig["world_size"] == 4 and sig["pending_size"] == 3
    # clearing the pending size (resize landed)
    membership_signal.write_signal(path, world_size=3, world_version=1)
    assert membership_signal.pending_size(path) is None


def test_speculative_compile_hits_on_actual_resize(spec, mesh8, tmp_path,
                                                   monkeypatch):
    """The tentpole flow, simulated multi-process via EDL_NUM_PROCESSES:
    steady state at world size 8 (1 device per process), master announces
    4 via the signal file, the speculative compiler precompiles the
    neighbor world EXECUTION-FREE, and the post-resize trainer's programs
    are all cache hits — counter-asserted, plus the AOT executable runs."""
    import jax

    from elasticdl_tpu.parallel.mesh import build_mesh

    monkeypatch.setenv("EDL_NUM_PROCESSES", "8")
    monkeypatch.setenv("EDL_PROCESS_ID", "0")
    cache = cc.CompileCache()
    batch = census_batch()
    devices = jax.devices()

    t_full = make_trainer(spec, mesh8, cache)
    state = t_full.init_state(batch)
    state, _ = t_full.train_step(state, batch)

    signal_path = str(tmp_path / "membership_signal.json")
    membership_signal.write_signal(signal_path, world_size=8, pending_size=4)

    compiled_meshes = {}

    def compile_for_size(size):
        if size < 1 or size > len(devices) or 16 % size:
            raise cc.SpeculativeCompiler.SkipSize(f"size {size}")
        mesh = build_mesh({"data": size}, devices[:size])
        t = make_trainer(spec, mesh, cache)
        abs_state = t.abstract_train_state(batch)
        t.aot_compile_train_step(abs_state, batch, speculative=True,
                                 abstract=True)
        compiled_meshes[size] = mesh

    speculator = cc.SpeculativeCompiler(
        compile_for_size, 8, max_size=len(devices), signal_path=signal_path
    )
    # the announced size is compiled first
    assert speculator.candidate_sizes()[0] == 4
    compiled = speculator.precompile_once()
    assert 4 in compiled
    assert cache.stats()["speculative_compiles"] >= 1

    # the resize lands: the new trainer re-traces NOTHING
    cache.reset_stats()
    from elasticdl_tpu.parallel import elastic

    new_mesh = compiled_meshes[4]
    # read before the handoff: leaves that keep their placement are handed
    # over by reference, and the next train_step donates them
    old_step = int(jax.device_get(state.step))
    handoff = elastic.LiveStateHandoff().capture(state)
    t_new = make_trainer(spec, new_mesh, cache)
    new_state = handoff.apply(new_mesh)
    new_state, logs = t_new.train_step(new_state, batch)
    stats = cache.stats()
    assert stats["misses"] == 0, stats
    assert stats["hits"] >= 1, stats
    assert stats["hit_rate"] == 1.0
    assert int(new_state.step) == old_step + 1
    assert np.isfinite(float(logs["loss"]))


def test_speculative_compiler_skips_and_failures_are_contained():
    calls = []

    def compile_for_size(size):
        calls.append(size)
        if size == 3:
            raise cc.SpeculativeCompiler.SkipSize("not representable")
        if size == 5:
            raise RuntimeError("boom")

    speculator = cc.SpeculativeCompiler(compile_for_size, 4)
    compiled = speculator.precompile_once()
    assert compiled == []                  # 3 skipped, 5 failed
    assert sorted(calls) == [3, 5]
    # neither is retried while the candidate set is unchanged
    assert speculator.precompile_once() == []
    assert sorted(calls) == [3, 5]
    # a resize resets both sets
    speculator.notify_resize(6)
    speculator.precompile_once()
    assert 7 in calls


def test_process_manager_announces_pending_size(tmp_path):
    """add/remove_worker on a cohort manager write the pending-membership
    signal file (no spawn happens until the watch loop acts), and spawned
    workers would inherit its path via EDL_PENDING_WORLD_FILE."""
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.master.process_manager import ProcessManager

    cfg = JobConfig(model_def="x", num_processes=2)
    mgr = ProcessManager(cfg, log_dir=str(tmp_path / "logs"))
    path = mgr._signal_path
    assert path.endswith("membership_signal.json")

    assert mgr.add_worker() == 3
    sig = membership_signal.read_signal(path)
    assert sig["world_size"] == 2 and sig["pending_size"] == 3
    assert membership_signal.pending_size(path) == 3

    assert mgr.remove_worker() == 2
    assert mgr.remove_worker() == 1
    assert membership_signal.pending_size(path) == 1
    assert mgr.pending_size() == 1


# ------------------------------------------------------------------ #
# live state handoff


def test_live_handoff_bitexact_vs_checkpoint_restore(spec, mesh8, tmp_path):
    """The acceptance gate: skipping the restore round trip changes no
    bit of the params (or opt state)."""
    import jax

    from elasticdl_tpu.parallel import elastic
    from elasticdl_tpu.parallel.mesh import build_mesh
    from elasticdl_tpu.training.checkpoint import CheckpointManager

    cache = cc.CompileCache()
    batch = census_batch()
    t_full = make_trainer(spec, mesh8, cache)
    state = t_full.init_state(batch)
    for i in range(2):
        state, _ = t_full.train_step(state, census_batch(seed=i))

    mngr = CheckpointManager(str(tmp_path / "ckpt"))
    mngr.save(state, wait=True)

    new_mesh = build_mesh({"data": 4}, jax.devices()[:4])
    t_new = make_trainer(spec, new_mesh, cache)
    restored = mngr.restore(t_new.abstract_train_state(batch))

    handoff = elastic.LiveStateHandoff().capture(state)
    assert handoff.step == 2
    handed = handoff.apply(new_mesh)
    assert not handoff.captured            # one-shot

    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get((handed.params,
                                                  handed.opt_state))),
        jax.tree_util.tree_leaves(jax.device_get((restored.params,
                                                  restored.opt_state))),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # every handed leaf lives on the new mesh
    for leaf in jax.tree_util.tree_leaves(handed.params):
        assert set(leaf.sharding.device_set) <= set(new_mesh.devices.flat)
    mngr.close()


def test_restore_or_handoff_prefers_fresh_capture(spec, mesh8, tmp_path):
    """restore_or_handoff: a capture at least as new as the durable step
    is applied (no restore); an older capture is discarded and restore
    wins."""
    import jax

    from elasticdl_tpu.parallel import elastic
    from elasticdl_tpu.parallel.mesh import build_mesh
    from elasticdl_tpu.training.checkpoint import CheckpointManager

    cache = cc.CompileCache()
    batch = census_batch()
    t_full = make_trainer(spec, mesh8, cache)
    state = t_full.init_state(batch)
    state, _ = t_full.train_step(state, batch)       # step 1
    stale = elastic.LiveStateHandoff().capture(state)
    state, _ = t_full.train_step(state, batch)       # step 2

    mngr = CheckpointManager(str(tmp_path / "ckpt"))
    mngr.save(state, wait=True)                      # durable step 2

    new_mesh = build_mesh({"data": 4}, jax.devices()[:4])
    t_new = make_trainer(spec, new_mesh, cache)
    abstract = t_new.abstract_train_state(batch)

    # stale capture (step 1) loses to the durable step 2
    got = mngr.restore_or_handoff(abstract, stale, new_mesh)
    assert int(jax.device_get(got.step)) == 2
    assert not stale.captured

    # fresh capture (step 2 == durable step 2) wins without a restore
    fresh = elastic.LiveStateHandoff().capture(state)
    got2 = mngr.restore_or_handoff(abstract, fresh, new_mesh)
    assert int(jax.device_get(got2.step)) == 2
    assert mngr.last_restored_step == 2
    mngr.close()


def test_save_overlapped_runs_teardown_during_write(spec, mesh8, tmp_path):
    from elasticdl_tpu.training.checkpoint import CheckpointManager

    cache = cc.CompileCache()
    batch = census_batch()
    t = make_trainer(spec, mesh8, cache)
    state = t.init_state(batch)
    mngr = CheckpointManager(str(tmp_path / "ckpt"))
    ran = []
    step = mngr.save_overlapped(state, lambda: ran.append(True))
    assert ran == [True]
    assert mngr.latest_step(refresh=True) == step
    # overlap work failing must not lose the durable checkpoint
    state2, _ = t.train_step(state, batch)

    def boom():
        raise RuntimeError("teardown failed")

    step2 = mngr.save_overlapped(state2, boom)
    assert mngr.latest_step(refresh=True) == step2
    mngr.close()


def test_stage_to_host_scopes_snapshot_to_changed_owners(spec, mesh8):
    """stage_to_host pulls ONLY leaves owned (partly) outside the
    surviving device set; fully-surviving leaves stay on device."""
    import jax

    from elasticdl_tpu.parallel import elastic
    from elasticdl_tpu.parallel.mesh import build_mesh

    cache = cc.CompileCache()
    batch = census_batch()
    t = make_trainer(spec, mesh8, cache)
    state = t.init_state(batch)

    surviving = [d.id for d in jax.devices()[:4]]
    handoff = elastic.LiveStateHandoff().capture(state)
    staged = handoff.stage_to_host(surviving)
    # replicated/sharded leaves over all 8 devices all have owners outside
    # the surviving half, so something must stage; the applied result is
    # still bit-exact on the new mesh
    assert staged > 0
    new_mesh = build_mesh({"data": 4}, jax.devices()[:4])
    handed = handoff.apply(new_mesh)
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(handed.params)),
        jax.tree_util.tree_leaves(jax.device_get(state.params)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------------ #
# worker in-place rescale (prefetch drain + live handoff + cache reuse)


def test_worker_inplace_rescale_preserves_state_and_hits_cache(monkeypatch):
    import jax

    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.worker.worker import Worker

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = JobConfig(
        model_zoo=os.path.join(repo, "model_zoo"),
        model_def="census.wide_deep.custom_model",
        minibatch_size=16,
    )
    worker = Worker(cfg)
    worker._build_trainer()
    batch = census_batch()
    worker._ensure_state(batch)
    state_before = jax.device_get(worker._state.params)
    worker._state, _ = worker._trainer.train_step(worker._state, batch)
    step_before = int(jax.device_get(worker._state.step))

    worker.request_rescale({"data": 4}, jax.devices()[:4])
    worker._rescale_in_place()
    assert worker.last_recovery_s is not None
    assert dict(zip(worker._mesh.axis_names,
                    worker._mesh.devices.shape)) == {"data": 4}
    assert int(jax.device_get(worker._state.step)) == step_before
    # training continues on the new mesh with the handed-over state
    worker._state, logs = worker._trainer.train_step(worker._state, batch)
    assert np.isfinite(float(logs["loss"]))
    assert int(jax.device_get(worker._state.step)) == step_before + 1
    del state_before
