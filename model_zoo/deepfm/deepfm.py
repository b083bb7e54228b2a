"""DeepFM on Criteo-style data — parity config #4 and the bench flagship
(BASELINE.md north star: Criteo-1TB DeepFM to AUC 0.80 on v5e-32).

Reference parity: the reference's deepfm zoo model (model_zoo/deepfm/*,
using elasticdl.layers.Embedding against the PS tier with async SGD).
Rebuilt sync-DP (SURVEY.md §7 documents the semantic change): one shared
mesh-sharded embedding table for all 26 categorical fields (ids offset per
field), FM first+second order, and a bfloat16 DNN tower on the MXU.

Input features:
  "dense": (B, 13) float32 raw counts (log1p applied on device)
  "cat":   (B, 26) int32 raw categorical values (hashed on device into
           per-field buckets — the Hashing-layer trick that bounds the table)
Labels: (B,) {0,1} click. Output: (B,) logits.
"""

import functools
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.api import feature_spec as fs
from elasticdl_tpu.api.layers import Embedding
from elasticdl_tpu.training import metrics as metrics_lib

NUM_DENSE = 13
NUM_CAT = 26


@functools.lru_cache(maxsize=None)
def feature_spec(field_vocab: int) -> fs.FeatureSpec:
    """The Criteo schema as data: 13 log-squashed integer counts + 26
    device-hashed categorical fields sharing one offset id space of
    NUM_CAT * field_vocab rows. All sources are packed-array columns, so
    the WHOLE spec runs as the device half inside the jitted step (zero
    host preprocessing beyond wire decode)."""
    return fs.FeatureSpec(
        [fs.numeric(f"i{j}", log1p=True, source=("dense", j))
         for j in range(NUM_DENSE)]
        + [fs.hashed(f"c{j}", field_vocab, source=("cat", j))
           for j in range(NUM_CAT)]
    )


class DeepFM(nn.Module):
    field_vocab: int = 100_000        # hash buckets per categorical field
    embedding_dim: int = 16
    hidden: Tuple[int, ...] = (400, 400)
    dropout: float = 0.0
    compute_dtype: jnp.dtype = jnp.bfloat16
    embedding_mode: str = "manual"

    @nn.compact
    def __call__(self, feats, training: bool = False):
        # the declared Criteo spec IS the in-model transform: log1p dense,
        # per-field hash + shared-id-space offsets, fused into the step
        spec = feature_spec(self.field_vocab)
        t = spec.device_transform({"dense": feats["dense"], "cat": feats["cat"]})
        dense, ids = t["dense"], t["cat"]                         # (B,13) (B,26)
        vocab = spec.total_vocab

        # ONE shared table carries both the D-dim FM/DNN vectors and the
        # per-id first-order weight as column D (round-5 chip finding: the
        # separate 1-wide fm_linear table cost a second full
        # gather+backward-scatter pass, ~5 ms/step of the 41 ms DeepFM
        # step — gather/scatter cost is per-ROW, so a 17th column is free)
        emb_all = Embedding(
            vocab, self.embedding_dim + 1, mode=self.embedding_mode,
            name="fm_embedding",
        )(ids)                                                  # (B, 26, D+1)
        emb, lin = emb_all[..., :-1], emb_all[..., -1]

        # the scopes below are names in a device trace (metadata only), as
        # ops/embedding.py's `emb/bwd/*` are
        with jax.named_scope("criteo/fm"):
            # FM second order: 0.5 * ((Σ_f v_f)^2 − Σ_f v_f^2), summed over D
            sum_v = jnp.sum(emb, axis=1)
            fm2 = 0.5 * jnp.sum(
                sum_v * sum_v - jnp.sum(emb * emb, axis=1), axis=-1)

            first_order = jnp.sum(lin, axis=1) + nn.Dense(
                1, dtype=jnp.float32, name="dense_linear"
            )(dense).reshape(-1)

        with jax.named_scope("criteo/tower"):
            x = jnp.concatenate(
                [emb.reshape(emb.shape[0], -1), dense], axis=-1
            ).astype(self.compute_dtype)
            for i, h in enumerate(self.hidden):
                x = nn.Dense(h, dtype=self.compute_dtype, name=f"dnn_{i}")(x)
                x = nn.relu(x)
                if self.dropout > 0:
                    x = nn.Dropout(
                        self.dropout, deterministic=not training)(x)
            dnn_out = nn.Dense(
                1, dtype=jnp.float32, name="dnn_out")(x).reshape(-1)

        bias = self.param("bias", nn.initializers.zeros, (1,), jnp.float32)
        return first_order + fm2.astype(jnp.float32) + dnn_out + bias[0]


def custom_model(**kwargs):
    return DeepFM(
        field_vocab=int(kwargs.get("field_vocab", 100_000)),
        embedding_dim=int(kwargs.get("embedding_dim", 16)),
        hidden=tuple(
            int(h) for h in str(kwargs.get("hidden", "400,400")).split(",")
        ),
        dropout=float(kwargs.get("dropout", 0.0)),
        compute_dtype=jnp.dtype(kwargs.get("compute_dtype", "bfloat16")),
        embedding_mode=str(kwargs.get("embedding_mode", "manual")),
    )


def loss(labels, outputs):
    with jax.named_scope("criteo/loss"):
        return optax.sigmoid_binary_cross_entropy(
            outputs, jnp.asarray(labels, jnp.float32).reshape(-1)
        )


def optimizer(**kwargs):
    from elasticdl_tpu.training import lr_modulation

    # modulated: runtime LR control (elastic rescale / master pushes)
    return lr_modulation.modulated(
        optax.adam, learning_rate=float(kwargs.get("learning_rate", 1e-3)))


def dataset_fn(mode, metadata):
    """Batch-parse Criteo records (data/parsing.py batch-parser contract).

    Two wire formats, picked by reader metadata: fixed-width binary .cbin
    shards (written once by `parsing.convert_criteo_tsv`; decoded at memcpy
    speed — the production path, mirroring the reference's RecordIO binary
    shards) and raw TSV (label \\t 13 ints \\t 26 hex categoricals; decoded
    by the C++ kernel in data/native/batch_parse.cc). The round-2 per-record
    Python loop capped the pipeline ~26x below the chip (BASELINE.md)."""
    from elasticdl_tpu.data import parsing

    if metadata and "record_bytes" in metadata:
        expect = parsing.criteo_bin_record_bytes(NUM_DENSE, NUM_CAT)
        if metadata["record_bytes"] != expect:
            raise ValueError(
                f"binary reader record_bytes={metadata['record_bytes']} does "
                f"not match the Criteo layout ({expect})"
            )
        return parsing.criteo_bin_batch_parser(NUM_DENSE, NUM_CAT)
    return parsing.criteo_batch_parser(num_dense=NUM_DENSE, num_cat=NUM_CAT)


def prediction_outputs_processor():
    """Prediction-job hook (reference zoo modules exposed the same factory):
    streams each minibatch's outputs to EDL_PREDICT_OUT (default
    ./predictions) as per-worker .npy files."""
    import os

    from elasticdl_tpu.worker.prediction_outputs_processor import (
        NpyPredictionOutputsProcessor,
    )

    return NpyPredictionOutputsProcessor(
        os.environ.get("EDL_PREDICT_OUT", "predictions")
    )


def eval_metrics_fn():
    return {"auc": metrics_lib.AUC(), "accuracy": metrics_lib.Accuracy()}
