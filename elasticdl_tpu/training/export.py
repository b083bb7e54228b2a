"""Model export for serving — the reference's model handler, rebuilt.

Reference parity: elasticdl/python/common/model_handler.py — after training,
the reference rewrote `elasticdl.layers.Embedding` into `tf.keras.layers.
Embedding` by pulling every table row from the parameter-server pods, then
wrote a TF SavedModel for serving. Here the trained state already holds the
full tables as mesh-sharded `jax.Array`s in HBM, so export is a gather-free
`device_get` of the state pytree:

  <export_dir>/params.msgpack   flax.serialization of {"params", "extra_vars"}
  <export_dir>/model_info.json  model_def, model_params, step, framework info

`load_model()` rebuilds the serving pair (flax Module, variables) from an
export directory — single-device inference needs no mesh. `export_saved_model`
additionally writes a TF SavedModel via jax2tf when TensorFlow is available,
matching the reference's serving artifact format.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from elasticdl_tpu.common.log_utils import default_logger
from elasticdl_tpu.version import __version__

logger = default_logger(__name__)

PARAMS_FILE = "params.msgpack"
INFO_FILE = "model_info.json"


def _host_variables(state: Any) -> Dict[str, Any]:
    """Gather the trained variables to host numpy. Single-host sharded arrays
    assemble via device_get; multi-host (jax.distributed) arrays span
    non-addressable devices, so they go through process_allgather instead."""
    import flax.linen as nn

    tree = {"params": state.params, "extra_vars": dict(state.extra_vars)}
    tree = nn.meta.unbox(tree)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        tree = multihost_utils.process_allgather(tree, tiled=True)
    return jax.tree_util.tree_map(lambda x: np.asarray(jax.device_get(x)), tree)


def export_model(
    state: Any,
    export_dir: str,
    model_def: str = "",
    model_params: Optional[Dict[str, Any]] = None,
    module_name: str = "",
    write_files: bool = True,
) -> str:
    """Write a serving export of a trained TrainState. Returns export_dir.

    Multi-process: the host gather inside is COLLECTIVE (process_allgather),
    so every process must call this; pass write_files=False on non-leader
    processes so only one writes the artifact.
    """
    from flax import serialization

    export_dir = os.path.abspath(export_dir)
    tree = _host_variables(state)
    if not write_files:
        return export_dir
    os.makedirs(export_dir, exist_ok=True)
    with open(os.path.join(export_dir, PARAMS_FILE), "wb") as f:
        f.write(serialization.msgpack_serialize(tree))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(tree["params"]))
    info = {
        "format": "elasticdl-tpu-export-v1",
        "model_def": model_def,
        "module_name": module_name,
        "model_params": dict(model_params or {}),
        "step": int(state.model_version),
        "num_params": int(n_params),
        "framework_version": __version__,
        "jax_version": jax.__version__,
    }
    # the info sidecar is what read_info/load_for_serving trust to decode
    # PARAMS_FILE — land it atomically so a crash mid-export can't leave a
    # torn manifest next to a complete params blob (edl-lint EDL305)
    info_path = os.path.join(export_dir, INFO_FILE)
    tmp = info_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(info, f, indent=2, default=str)
    os.replace(tmp, info_path)
    logger.info(
        "exported model (%.3fM params, step %d) -> %s",
        n_params / 1e6, info["step"], export_dir,
    )
    return export_dir


def read_info(export_dir: str) -> Dict[str, Any]:
    with open(os.path.join(export_dir, INFO_FILE)) as f:
        return json.load(f)


def load_variables(export_dir: str) -> Dict[str, Any]:
    """Restore the exported variables dict {"params", "extra_vars"} as host
    numpy pytrees (no target structure needed)."""
    from flax import serialization

    with open(os.path.join(export_dir, PARAMS_FILE), "rb") as f:
        return serialization.msgpack_restore(f.read())


def load_model(
    export_dir: str,
    model_zoo: str,
    model_def: str = "",
    model_params: Optional[Dict[str, Any]] = None,
) -> Tuple[Any, Dict[str, Any]]:
    """Rebuild (module, variables) for serving from an export directory.

    `model.apply(variables, features, training=False)` is the serving call;
    model_def/model_params default to the values recorded at export time.
    """
    from elasticdl_tpu.common.model_utils import load_module

    info = read_info(export_dir)
    model_def = model_def or info["model_def"]
    params = dict(info.get("model_params", {}))
    params.update(model_params or {})
    module, func_name = load_module(model_zoo, model_def)
    model = getattr(module, func_name)(**params)
    tree = load_variables(export_dir)
    variables = {"params": tree["params"], **tree.get("extra_vars", {})}
    return model, variables


def export_saved_model(
    export_dir: str,
    model_zoo: str,
    example_features: Any,
    out_dir: Optional[str] = None,
) -> Optional[str]:
    """Convert an export directory into a TF SavedModel via jax2tf.

    Returns the SavedModel path, or None when TensorFlow/jax2tf is not
    usable in this environment (the msgpack export remains authoritative).
    """
    try:
        import tensorflow as tf
        from jax.experimental import jax2tf
    except Exception as e:  # pragma: no cover - env without TF
        logger.warning("SavedModel export unavailable: %s", e)
        return None

    model, variables = load_model(export_dir, model_zoo)

    def serve(features):
        return model.apply(variables, features, training=False)

    # symbolic batch dim "b" so one SavedModel signature serves any batch size
    poly = jax.tree_util.tree_map(
        lambda x: ", ".join(["b"] + ["_"] * (np.ndim(x) - 1)), example_features
    )
    tf_fn = tf.function(
        jax2tf.convert(serve, with_gradient=False, polymorphic_shapes=[poly]),
        autograph=False,
        input_signature=[
            jax.tree_util.tree_map(
                # leading dim None: serving batch size is the client's choice
                lambda x: tf.TensorSpec(
                    (None,) + tuple(np.shape(x)[1:]), np.asarray(x).dtype
                ),
                example_features,
            )
        ],
    )
    out_dir = out_dir or os.path.join(export_dir, "saved_model")
    module = tf.Module()
    module.serve = tf_fn
    tf.saved_model.save(module, out_dir)
    logger.info("SavedModel -> %s", out_dir)
    return out_dir
