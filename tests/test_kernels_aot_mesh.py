"""REHEARSAL, no chip: the zoo's DeepFM `train_many`, Adam and all, compiled
for ALL FOUR chips of the described v5e:2x2 on both schedules of the manual
lookup (`ops/embedding.py`: `data=4` routed, `data=2,model=2` gathered), and
the text read for where the table shard's gradient goes. It comes out of the
lookup's backward `lax.cond`s; XLA moves an elementwise user of a
conditional's result into its branches, and before PR 61 that user was Adam's
`g * g`: a table-sized plane written at the end of each branch and read back by
the optimizer's pass as one operand more (PERF.md §6, PR 61).
`embedding._fence_cotangent` keeps every user of the gradient outside; these
cases fail without it. The size — 26 fields of 65 536 rows, 1024 examples a
chip — is the smallest at which the unfenced program shows the defect as the
four-chip cell's does. Nothing runs: no time, no result.

The fixtures are `tests/test_kernels_aot.py`'s; a file of its own so that a
third xdist worker shares the compiles (`ALLOW_MULTIPLE_LIBTPU_LOAD=1`)."""

import os
import sys

import jax
import numpy as np
import pytest

from tests.test_kernels_aot import no_compile_cache  # noqa: F401  (fixture)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402

pytestmark = pytest.mark.usefixtures("xla_optimises")

# the four-chip cell's widths (benchmark/configs/deepfm-criteo1tb.json) at a
# vocabulary a test can afford: 1 703 936 rows, 425 984 a shard, x 11 columns
MODEL_PARAMS = "embedding_dim=10;hidden=400,400,400;field_vocab=65536"
SHARD = "f32[425984,11]"
BATCH_PER_CHIP, STEPS = 1024, 2
MESHES = {"data=4": "routed", "data=2,model=2": "gathered"}


@pytest.fixture(scope="module")
def four_chips():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return list(topo.devices)


@pytest.fixture(scope="module")
def compiled():
    """mesh -> the compiled text's computations: one compile a mesh."""
    return {}


@pytest.fixture
def program(request, four_chips, no_compile_cache, monkeypatch, compiled):
    mesh_shape = request.param
    if mesh_shape not in compiled:
        from elasticdl_tpu.common.config import JobConfig
        from elasticdl_tpu.parallel import mesh as mesh_lib
        from elasticdl_tpu.training.model_spec import ModelSpec
        from elasticdl_tpu.training.trainer import Trainer

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # the routes ask
        cfg = JobConfig.from_argv([
            "--model_zoo", os.path.join(REPO_ROOT, "model_zoo"),
            "--model_def", "deepfm.deepfm.custom_model",
            "--model_params", MODEL_PARAMS, "--mesh_shape", mesh_shape])
        spec = ModelSpec.from_config(cfg)
        mesh = mesh_lib.build_job_mesh(cfg, four_chips)
        trainer = Trainer(spec, mesh)
        batch = BATCH_PER_CHIP * len(four_chips)
        example = {"features": {"dense": np.zeros((batch, 13), np.float32),
                                "cat": np.zeros((batch, 26), np.int32)},
                   "labels": np.zeros((batch,), np.int32),
                   "mask": np.ones((batch,), np.float32)}
        exe = trainer.aot_compile_train_many(
            trainer.abstract_train_state(example),
            mesh_lib.abstract_batch_stack(mesh, example, STEPS, spec.batch_partition))
        compiled[mesh_shape] = chip_smoke.hlo_computations(exe.as_text())
    computations = compiled[mesh_shape]
    # the routed schedule alone traces `emb/route/*` scopes
    routed = any("emb/route/" in inst["op_name"] for inst in instructions(computations))
    assert routed == (MESHES[mesh_shape] == "routed")
    return computations


def instructions(computations):
    return [inst for insts in computations.values() for inst in insts]


def shards_in(shape):
    return shape.count(SHARD)


both_meshes = pytest.mark.parametrize("program", sorted(MESHES), indirect=True)


@both_meshes
def test_the_lookup_s_conditionals_return_one_table_shard(program):
    """The gradient and nothing beside it: a second array of the shard's
    shape in a conditional's result is work carried in from outside."""
    conditionals = [inst for inst in instructions(program)
                    if inst["opcode"] == "conditional" and "shard_map/" in inst["op_name"]]
    assert conditionals
    assert max(shards_in(inst["shape"]) for inst in conditionals) == 1, [
        (inst["name"], inst["shape"]) for inst in conditionals if shards_in(inst["shape"]) > 1]


@both_meshes
def test_nothing_of_the_optimizer_stands_in_a_branch_of_the_lookup(program):
    """No un-fused instruction of the shard's shape traced under `optimizer/`
    lies in a branch computation, and `chip_smoke.py --child lookup`'s counter
    of the same defect reads 0 on this text."""
    branches = {name for inst in instructions(program) if inst["opcode"] == "conditional"
                for name in inst["calls"]}
    assert branches
    stray = [(name, inst["name"]) for name in sorted(branches) for inst in program[name]
             if inst["shape"].startswith(SHARD) and "optimizer/" in inst["op_name"]]
    assert stray == []
    assert chip_smoke.foreign_ops_in_lookup_branches(program, SHARD) == []


@both_meshes
def test_adam_s_square_is_inside_the_pass_that_writes_the_table_s_three_planes(program):
    """`optimizer/integer_pow` of the shard's shape is an instruction of ONE
    fused computation, and the fusion that runs it writes the parameters and
    both moments: g is read once, g * g is never a plane of its own."""
    holders = {name for name, insts in program.items() for inst in insts
               if inst["shape"].startswith(SHARD) and "optimizer/integer_pow" in inst["op_name"]}
    assert len(holders) == 1, holders
    callers = [inst for inst in instructions(program) if holders & set(inst["calls"])]
    assert [inst["opcode"] for inst in callers] == ["fusion"], callers
    assert shards_in(callers[0]["shape"]) == 3, callers[0]["shape"]
    # and the gradient it reads is no copy of the conditional's result
    assert not [inst["name"] for inst in instructions(program)
                if inst["opcode"] == "copy" and inst["shape"].startswith(SHARD)]
