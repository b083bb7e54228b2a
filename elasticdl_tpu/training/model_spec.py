"""The model-zoo contract, resolved into one object.

Reference parity: the reference's model-zoo module contract — module-level
`custom_model()`, `loss()`, `optimizer()`, `dataset_fn()`, `eval_metrics_fn()`,
`callbacks()` functions addressed by `--model_def=pkg.module.custom_model`
(reference: elasticdl/python/common/model_utils.py and model_zoo/*).

Rebuilt in JAX terms:
- `custom_model(**model_params)` returns a `flax.linen.Module`,
- `loss(labels, outputs)` returns a scalar `jnp` loss (mean over batch) or a
  per-example vector, or a dict of them whose `loss` entry is minimised and
  whose other entries the train step reports beside it; `outputs` is what the
  module returns, one array or a pytree of them,
- `optimizer(**model_params)` returns an `optax.GradientTransformation`,
- `dataset_fn(mode, metadata)` returns a `parse_fn(raw_record) -> (features,
  label)` of numpy values with static shapes (XLA needs static shapes; the
  framework does the batching and last-batch padding),
- `eval_metrics_fn()` returns `{name: Metric}` using
  `elasticdl_tpu.training.metrics` streaming metrics,
- `callbacks()` (optional) returns a list of callback objects.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import flax.linen as nn

from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.common.model_utils import get_module_attr, load_module


@dataclass
class ModelSpec:
    model: nn.Module
    loss: Callable[..., Any]
    optimizer: Any                       # optax.GradientTransformation
    dataset_fn: Optional[Callable[..., Any]]
    eval_metrics_fn: Optional[Callable[[], Dict[str, Any]]]
    callbacks: List[Any] = field(default_factory=list)
    prediction_outputs_processor: Optional[Any] = None
    module_name: str = ""
    # The params the model was ACTUALLY built with (cfg.model_params plus
    # injected defaults like compute_dtype) — export must record these, or a
    # serving reload could rebuild the module with different defaults.
    model_params: Dict[str, Any] = field(default_factory=dict)
    # Optional per-top-level-key PartitionSpec overrides for input batches
    # (zoo module-level `batch_partition()`; sequence-parallel models shard
    # tokens over ('data', 'seq')).
    batch_partition: Optional[Dict[str, Any]] = None
    # Weight on auxiliary losses sown into the "losses" collection (e.g.
    # api.layers.MoE's Switch load-balance penalty). 0 = ignored. The
    # trainer adds weight * sum(sown leaves) INSIDE the differentiated
    # loss, so the aux regularizes training. Zoo modules export it as a
    # module-level `aux_loss_weight` float.
    aux_loss_weight: float = 0.0
    # Sown terms the step reports BY NAME beside their sum: {name in the
    # "losses" collection: name in the step's metrics}, each times
    # `aux_loss_weight` (a model with two auxiliary terms — a load balance and
    # an indexer's loss, say — whose step would else report one number for
    # both). Zoo modules export it as a module-level `aux_loss_terms` dict.
    aux_loss_terms: Dict[str, str] = field(default_factory=dict)

    @classmethod
    def from_config(cls, cfg: JobConfig) -> "ModelSpec":
        module, func_name = load_module(cfg.model_zoo, cfg.model_def)
        model_fn = getattr(module, func_name, None)
        if model_fn is None:
            raise ValueError(f"{cfg.model_def!r}: no {func_name} in {module.__name__}")
        # Convention: the job-level compute_dtype reaches user models through
        # model_params unless the user already set one explicitly.
        model_params = dict(cfg.model_params)
        model_params.setdefault("compute_dtype", cfg.compute_dtype)
        model = model_fn(**model_params)
        if not isinstance(model, nn.Module):
            raise TypeError(
                f"{cfg.model_def} must return a flax.linen.Module, got {type(model)}"
            )

        loss = get_module_attr(module, "loss", cfg.loss, required=True)
        opt_fn = get_module_attr(module, "optimizer", cfg.optimizer, required=True)
        dataset_fn = get_module_attr(module, "dataset_fn", cfg.dataset_fn, required=False)
        metrics_fn = get_module_attr(
            module, "eval_metrics_fn", cfg.eval_metrics_fn, required=False
        )
        callbacks_fn = get_module_attr(module, "callbacks", "", required=False)
        batch_partition_fn = get_module_attr(
            module, "batch_partition", "", required=False
        )
        pop_fn = get_module_attr(
            module,
            "prediction_outputs_processor",
            cfg.prediction_outputs_processor,
            required=False,
        )

        return cls(
            model=model,
            loss=loss,
            optimizer=opt_fn(**cfg.model_params) if opt_fn else None,
            dataset_fn=dataset_fn,
            eval_metrics_fn=metrics_fn,
            callbacks=list(callbacks_fn()) if callbacks_fn else [],
            prediction_outputs_processor=pop_fn() if pop_fn else None,
            module_name=module.__name__,
            model_params=model_params,
            batch_partition=(
                dict(batch_partition_fn()) if batch_partition_fn else None
            ),
            aux_loss_weight=float(
                getattr(module, "aux_loss_weight", 0.0) or 0.0),
            aux_loss_terms=dict(getattr(module, "aux_loss_terms", None) or {}),
        )
