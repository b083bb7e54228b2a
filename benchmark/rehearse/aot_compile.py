"""Rehearsal, no chip: AOT-compile a configuration's `train_many` at full size
for a described v5e and print the compiler's `memory_analysis()`.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse/aot_compile.py \
        --config deepfm-criteo [--batch_per_chip 8192] [--steps 32] \
        [--field_vocab N] [--one_chip]

It proves that the chip's compiler accepts the program and says how many
bytes it needs on each device. Nothing runs: it gives no time and no result,
and what it prints is never reported as a chip run. `--one_chip` compiles a
four-chip configuration for one chip, to show that it does not fit there.
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


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--batch_per_chip", type=int, default=8192)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--field_vocab", type=int, default=0,
                    help="override the configuration's field_vocab (to find the size that fits)")
    ap.add_argument("--one_chip", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    config = common.load_json("configs", args.config + ".json")
    if args.field_vocab:
        params = common.model_params(config)
        params["field_vocab"] = str(args.field_vocab)
        config["model_params"] = common.format_model_params(params)
    chips = 1 if args.one_chip else int(config["chips"])
    if chips == 1:
        config["mesh_shape"] = ""
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices)[:chips]
    # the kernels ask jax.default_backend() whether they may run; steer them
    # here, in the rehearsal, not through an option of the program
    jax.default_backend = lambda: "tpu"

    resident = common.load_module("drivers", "resident")
    from elasticdl_tpu.parallel import mesh as mesh_lib

    _, spec, mesh, trainer = resident.build_trainer(config, devices, seed=0)
    batch = args.batch_per_chip * chips
    example = {
        "features": {"dense": np.zeros((batch, 13), np.float32),
                     "cat": np.zeros((batch, 26), np.int32)},
        "labels": np.zeros((batch,), np.int32),
        "mask": np.ones((batch,), np.float32),
    }
    state = trainer.abstract_train_state(example)
    stacked = mesh_lib.abstract_batch_stack(
        mesh, example, args.steps, spec.batch_partition)
    exe = trainer.aot_compile_train_many(state, stacked)
    mem = exe.memory_analysis()
    text = exe.as_text()
    gib = 1 << 30
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"REHEARSAL (no chip): {args.config} model_params={config['model_params']} "
          f"chips={chips} batch={batch} steps={args.steps}")
    print(f"  per device: arguments {mem.argument_size_in_bytes / gib:.3f} GiB, "
          f"outputs {mem.output_size_in_bytes / gib:.3f} GiB, "
          f"aliased {mem.alias_size_in_bytes / gib:.3f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / gib:.3f} GiB, "
          f"program {mem.generated_code_size_in_bytes / gib:.3f} GiB "
          f"=> {total / gib:.3f} GiB")
    print(f"  Mosaic custom calls: {text.count('tpu_custom_call')}; collectives: "
          + ", ".join(f"{name} {text.count(name + '(')}" for name in (
              "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
              "collective-permute")))


if __name__ == "__main__":
    main()
