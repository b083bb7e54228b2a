"""Plain reference for the zoo's xDeepFM (Lian et al., arXiv:1803.05170):
the Compressed Interaction Network written as the paper's equation 6 — the
outer product z[b,h,f,d] = x_k[b,h,d] * x_0[b,f,d], then one matrix product
over (h, f) — in float32, with the linear part, the tower, the loss and the
hand-written Adam of `reference/deepfm.py`.

Departures, all the zoo's: identity activation in the CIN and every feature
map fed both to the next layer and to the output (the paper's own best
setting and its `direct` connection); no FM second-order term (xDeepFM has
none); the shared hashed table and log1p continuous features of the DeepFM
reference.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmark import common

_deepfm = common.load_module("reference", "deepfm")

TABLE = ("embedding", "table")

# As in reference/deepfm.py. CIN runs in bfloat16 (f32 accumulation) in the
# program, three layers deep, so its rounding reaches the logit and every
# gradient (read on the chip, PR 22: loss 1.8e-5 to 1.7e-4; last-column
# median 3.0e-4 to 5.0e-4 over five seeds, 1.31e-3 with the `lo` term dropped;
# latent-column median 1.4e-2 and 3.4e-2, set by CIN's bf16 and unmoved by
# `lo`).
TOLERANCES = dict(
    _deepfm.TOLERANCES,
    mu_lin_rel_median=9e-4,
    mu_emb_rel_median=8e-2,
)

row_ids = _deepfm.row_ids
adam_step = _deepfm.adam_step


def cin(cin_params, x0):
    """x0: (B, F, D). Layer k: x_{k+1}[b,o,d] = sum_{h,f} W_k[o,h,f] *
    x_k[b,h,d] * x_0[b,f,d]; the output is every layer's sum over d."""
    xk, outs = x0, []
    for i in range(len(cin_params)):
        w = cin_params[f"w{i}"]                                 # (O, H_k * F)
        z = xk[:, :, None, :] * x0[:, None, :, :]               # (B, H_k, F, D)
        z = z.reshape(z.shape[0], -1, z.shape[-1])              # (B, H_k * F, D)
        xk = jnp.einsum("oj,bjd->bod", w, z)
        outs.append(jnp.sum(xk, axis=-1))
    return jnp.concatenate(outs, axis=-1)


def logits(params, batch):
    d = params["dense"]
    emb, lin, dense = _deepfm.lookup(params, batch)
    first = jnp.sum(lin, axis=1) + (
        dense @ d["dense_linear"]["kernel"] + d["dense_linear"]["bias"]).reshape(-1)
    cin_logit = (cin(d["CIN_0"], emb) @ d["cin_out"]["kernel"]
                 + d["cin_out"]["bias"]).reshape(-1)
    x = jnp.concatenate([emb.reshape(emb.shape[0], -1), dense], axis=-1)
    return first + cin_logit + _deepfm.tower(d, x, _deepfm.num_tower_layers(d)) \
        + d["bias"][0]


def loss_sum(params, batch):
    per_example = _deepfm.bce_with_logits(logits(params, batch), batch["labels"])
    return jnp.sum(per_example * batch["mask"].astype(jnp.float32))
