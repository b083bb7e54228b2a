"""xDeepFM — parity config #4b (reference model_zoo xdeepfm variant).

DeepFM plus a Compressed Interaction Network (CIN): explicit high-order
feature interactions, one matrix product over the (feature map, field)
outer-product plane for each embedding coordinate, in bfloat16 with float32
accumulation. Most of the step's arithmetic and, as XLA runs its backward,
most of its time (PERF.md sections 5 and 6, PR 40). Two routes, picked by
`ops/pallas_cin.py::cin_route` from what the code can see: on one TPU (or in
interpret mode) the Pallas kernels, which make the plane and its pulled-back
twin a tile at a time in VMEM; anywhere else the three-operand einsum below.
"""

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from elasticdl_tpu.ops import pallas_cin
from model_zoo.deepfm.deepfm import (
    DeepFM,
    dataset_fn,  # noqa: F401  (same Criteo record format)
    eval_metrics_fn,  # noqa: F401
    loss,  # noqa: F401
)


def cin_einsum(ws, x0):
    """CIN in `jax.numpy`: ws[i] (O_i, H_i * F) in the compute dtype, x0
    (B, F, D) -> (B, sum of O_i). One three-operand einsum a layer; XLA
    contracts it pairwise and, in the backward, writes the pulled-back
    (B, H, F, D) plane to HBM and reduces it twice (PERF.md section 6,
    PR 40). The route off the chip and across devices, and the reference
    `tests/test_pallas_cin.py` holds the kernels to."""
    xk, outs = x0, []
    for w in ws:
        wr = w.reshape(w.shape[0], xk.shape[1], x0.shape[1])
        xk = jnp.einsum("ohf,bhd,bfd->bod", wr, xk, x0)  # (B, O, D)
        outs.append(jnp.sum(xk, axis=-1))                # (B, O)
    return jnp.concatenate(outs, axis=-1)


class CIN(nn.Module):
    layer_sizes: Tuple[int, ...] = (128, 128)
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x0):
        # x0: (B, F, D)
        x0 = x0.astype(self.compute_dtype)
        ws, hk = [], x0.shape[1]
        for i, h in enumerate(self.layer_sizes):
            ws.append(self.param(
                f"w{i}",
                nn.initializers.glorot_uniform(),
                (h, hk * x0.shape[1]),
                jnp.float32,
            ))
            hk = h
        route = pallas_cin.cin_route(
            x0.shape, self.layer_sizes, self.compute_dtype,
            pallas_cin.runnable(), pallas_cin.ambient_devices())
        if route == "kernel":
            return pallas_cin.cin(ws, x0)
        return cin_einsum([w.astype(self.compute_dtype) for w in ws], x0)


class XDeepFM(nn.Module):
    base: DeepFM
    cin_sizes: Tuple[int, ...] = (128, 128)

    @nn.compact
    def __call__(self, feats, training: bool = False):
        from elasticdl_tpu.api.layers import Embedding
        from model_zoo.deepfm.deepfm import feature_spec

        base = self.base
        # same declared Criteo spec as DeepFM: identical id space, so the
        # two models share checkpoints' table geometry
        spec = feature_spec(base.field_vocab)
        t = spec.device_transform(
            {"dense": feats["dense"], "cat": feats["cat"]})
        dense, ids = t["dense"], t["cat"]
        vocab = spec.total_vocab

        # single table, linear weight as the last column (see
        # deepfm.DeepFM — halves the per-step gather+scatter row count)
        emb_all = Embedding(
            vocab, base.embedding_dim + 1, mode=base.embedding_mode,
            name="embedding",
        )(ids)
        emb, lin = emb_all[..., :-1], emb_all[..., -1]

        # scopes: names in a device trace (metadata only), as DeepFM's
        with jax.named_scope("criteo/fm"):
            first = jnp.sum(lin, axis=1) + nn.Dense(
                1, dtype=jnp.float32, name="dense_linear"
            )(dense).reshape(-1)

        with jax.named_scope("criteo/cin"):
            cin_out = CIN(self.cin_sizes, base.compute_dtype)(emb)
            cin_logit = nn.Dense(1, dtype=jnp.float32, name="cin_out")(
                cin_out.astype(jnp.float32)
            ).reshape(-1)

        with jax.named_scope("criteo/tower"):
            x = jnp.concatenate(
                [emb.reshape(emb.shape[0], -1), dense], axis=-1
            ).astype(base.compute_dtype)
            for i, h in enumerate(base.hidden):
                x = nn.Dense(h, dtype=base.compute_dtype, name=f"dnn_{i}")(x)
                x = nn.relu(x)
            dnn_logit = nn.Dense(
                1, dtype=jnp.float32, name="dnn_out")(x).reshape(-1)

        bias = self.param("bias", nn.initializers.zeros, (1,), jnp.float32)
        return first + cin_logit + dnn_logit + bias[0]


def custom_model(**kwargs):
    base = DeepFM(
        field_vocab=int(kwargs.get("field_vocab", 100_000)),
        embedding_dim=int(kwargs.get("embedding_dim", 16)),
        hidden=tuple(int(h) for h in str(kwargs.get("hidden", "400,400")).split(",")),
        compute_dtype=jnp.dtype(kwargs.get("compute_dtype", "bfloat16")),
        embedding_mode=str(kwargs.get("embedding_mode", "manual")),
    )
    cin = tuple(int(h) for h in str(kwargs.get("cin_sizes", "128,128")).split(","))
    return XDeepFM(base=base, cin_sizes=cin)


def optimizer(**kwargs):
    from elasticdl_tpu.training import lr_modulation

    return lr_modulation.modulated(
        optax.adam, learning_rate=float(kwargs.get("learning_rate", 1e-3)))
