"""Rehearsal, no chip: AOT-compile a language-model configuration's
`train_many` at full size for a described v5e chip, and the reference's
gradient program beside it, and print the compiler's `memory_analysis()`.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse/aot_compile_lm.py \
        --config olmoe-1b-7b --traffic resident-lm-4k [--batch_per_chip 2] [--steps 8]

It proves that the chip's compiler accepts the programs (the flash kernel at
head 128, the grouped matmuls) and says how many bytes each needs. Nothing
runs: it gives no time and no result, and what it prints is never reported as
a chip run.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402

GIB = 1 << 30


def _report(name, exe):
    mem = exe.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    text = exe.as_text()
    print(f"  {name}: arguments {mem.argument_size_in_bytes / GIB:.3f} GiB, outputs "
          f"{mem.output_size_in_bytes / GIB:.3f}, aliased {mem.alias_size_in_bytes / GIB:.3f}, "
          f"temporaries {mem.temp_size_in_bytes / GIB:.3f} => {total / GIB:.3f} GiB; "
          f"Mosaic custom calls {text.count('tpu_custom_call')}")
    return text


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--batch_per_chip", type=int, default=0)
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--reference", action="store_true",
                    help="also compile the reference's gradient program")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    config = common.load_json("configs", args.config + ".json")
    traffic = common.load_json("traffic", args.traffic + ".json")
    batch = args.batch_per_chip or int(traffic["batch_per_chip"])
    steps = args.steps or int(traffic["steps_per_dispatch"])
    seq = int(traffic["seq_len"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices)[:1]
    # the kernels ask jax.default_backend() whether they may run; steer them
    # here, in the rehearsal, not through an option of the program
    jax.default_backend = lambda: "tpu"

    resident = common.load_module("drivers", "resident")
    from elasticdl_tpu.parallel import mesh as mesh_lib

    _, spec, mesh, trainer = resident.build_trainer(config, devices, seed=0)
    example = {"features": np.zeros((batch, seq), np.int32),
               "labels": np.zeros((batch, seq), np.int32),
               "mask": np.ones((batch,), np.float32)}
    state = trainer.abstract_train_state(example)
    stacked = mesh_lib.abstract_batch_stack(mesh, example, steps, spec.batch_partition)
    print(f"REHEARSAL (no chip): {args.config} model_params={config['model_params']} "
          f"batch={batch} seq={seq} steps={steps}")
    text = _report("train_many", trainer.aot_compile_train_many(state, stacked))
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv", "ragged-dot"):
        print(f"    instructions named {kernel}*: {text.count('%' + kernel)}")

    if args.reference:
        from jax.sharding import SingleDeviceSharding

        ref = common.load_module("reference", common.model_name(config))
        hp = ref.hyper(common.model_params(config))
        one = SingleDeviceSharding(devices[0])
        params = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32, sharding=one),
            state.params)
        ref_batch = {
            "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one),
            "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one),
            "mask": jax.ShapeDtypeStruct((batch,), jnp.float32, sharding=one)}
        with jax.default_matmul_precision("highest"):
            grad = jax.jit(jax.value_and_grad(
                lambda p, b: ref.loss(p, b, hp), has_aux=True))
            _report("reference value_and_grad", grad.lower(params, ref_batch).compile())


if __name__ == "__main__":
    main()
