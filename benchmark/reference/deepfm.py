"""Plain reference for the zoo's DeepFM (Guo et al., arXiv:1703.04247):
forward, loss and gradients in float32 `jax.numpy`, `jnp.take` for the
lookup, `jax.grad`, and a hand-written Adam. No kernels, no `shard_map`, no
flax module, nothing imported from the program.

Departures from the paper, all the zoo's and all shape-neutral: the 26
categorical fields are hash-bucketed into equal ranges of one shared table
(murmur3's 32-bit finaliser, modulo `field_vocab`, plus the field's offset);
the first-order weight of an id is the table's last column; the 13 continuous
features enter as log1p(max(x, 0)) through one linear unit (first order) and
the tower's input, and take no part in the second-order term; no dropout
(the configuration's `changed`).

Parameters: {"rows": (U, D+1) the table rows the reference holds — compacted
and re-indexed, see `benchmark/check.py` — and "dense": every other
parameter under the names the program gives them}.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

TABLE = ("fm_embedding", "table")       # where the program keeps the table

# Tolerances of `benchmark/check.py`'s comparison, each with its reason.
# The reference is float32 throughout (matmuls at "highest"); the program
# runs the tower in bfloat16 with float32 accumulation and places embedding
# gradients with a two-term bf16 split (hi + lo, 16 mantissa bits, 4.6e-6 of
# max|ref| measured in PR 21). Set from the chip readings of PR 22 (PERF.md
# section 6 has them, beside the reading with the `lo` term dropped) at about
# twice the largest the correct program showed over the seeds run.
TOLERANCES = {
    # per-step loss, relative: the tower's bf16 rounding moves a logit by
    # ~1e-3 of the tower's output (read: 1.8e-4 to 3.0e-4); bf16
    # ACCUMULATION over 273 to 400 terms would move it ten times as far
    "loss_rel": 8e-4,
    # Adam's first moment of the touched rows is linear in the gradients
    # (Adam's update is not: its first steps are lr * sign(g)). The MEDIAN
    # over the rows of the relative error is the sharp figure: most rows are
    # hit once or twice, so a placement that keeps 8 mantissa bits instead of
    # 16 shows there at 2^-9 to 2^-10, about 1e-3, and a median over 1e5
    # rows does not move with the seed. Last column = first-order weights,
    # whose gradient dL/dlogit comes back through no bf16 arithmetic: read
    # 1.3e-4 to 3.1e-4 over five seeds, and 1.24e-3 with the `lo` term dropped
    # (EDL_EMB_PALLAS_PRECISION=bf16), which this tolerance refuses.
    "mu_lin_rel_median": 6e-4,
    # latent columns: their gradient comes back through the bf16 tower, whose
    # rounding sets the figure (read: 1.5e-3 and 1.7e-3; 2.3e-3 without `lo`)
    "mu_emb_rel_median": 4e-3,
    # The same in relative L2 over all touched rows. A hot row sums thousands
    # of contributions of both signs that nearly cancel, so its error is
    # large against what is left, and a few hot rows carry the norm: this
    # figure moves tenfold with the seed (read: 1.8e-5 to 4.0e-4 here,
    # 6e-4 to 7.6e-3 on xdeepfm). A gross check.
    "mu_lin_rel_l2": 5e-2,
    "mu_emb_rel_l2": 5e-2,
    # the update itself (rows and dense parameters after the last step less
    # their initial values), relative L2. Adam's first steps are
    # lr * sign(g) whatever |g| is, so an element whose gradient is near zero
    # flips with the last bit and costs 2 * lr: a fraction of a percent of
    # flipped elements is 10-20% here (read: 0.1-0.3% on the rows, 2.7-4.4% on
    # the dense parameters; xdeepfm 9.8%). Gross too — rows updated at the
    # wrong place, or a wrong optimizer, give about 1.4.
    "rows_update_rel_l2": 0.4,
    "dense_update_rel_l2": 0.4,
}

LEARNING_RATE, B1, B2, EPS = 1e-3, 0.9, 0.999, 1e-8     # the zoo's optax.adam


def hash_bucket(raw, num_bins: int):
    """murmur3 fmix32, then modulo: NumPy, on uint32, wrapping."""
    x = np.asarray(raw).astype(np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    x = x ^ (x >> np.uint32(16))
    return (x % np.uint32(num_bins)).astype(np.int64)


def row_ids(model_params: dict, cat_raw) -> np.ndarray:
    """Table row of every raw categorical value: (..., 26) int64."""
    field_vocab = int(model_params["field_vocab"])
    offsets = np.arange(cat_raw.shape[-1], dtype=np.int64) * field_vocab
    return hash_bucket(cat_raw, field_vocab) + offsets


def bce_with_logits(logits, labels):
    y = labels.astype(jnp.float32)
    return jnp.maximum(logits, 0.0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits)))


def lookup(params, batch):
    """Shared by deepfm and xdeepfm: latent vectors, first-order weights and
    the transformed continuous features."""
    e = jnp.take(params["rows"], batch["ids"], axis=0)          # (B, 26, D+1)
    dense = jnp.log1p(jnp.maximum(batch["dense"].astype(jnp.float32), 0.0))
    return e[..., :-1], e[..., -1], dense


def tower(dense_params, x, layers: int):
    for i in range(layers):
        p = dense_params[f"dnn_{i}"]
        x = jnp.maximum(x @ p["kernel"] + p["bias"], 0.0)
    p = dense_params["dnn_out"]
    return (x @ p["kernel"] + p["bias"]).reshape(-1)


def num_tower_layers(dense_params) -> int:
    return sum(1 for k in dense_params if k.startswith("dnn_") and k != "dnn_out")


def logits(params, batch):
    d = params["dense"]
    emb, lin, dense = lookup(params, batch)
    sum_v = jnp.sum(emb, axis=1)
    fm2 = 0.5 * jnp.sum(sum_v * sum_v - jnp.sum(emb * emb, axis=1), axis=-1)
    first = jnp.sum(lin, axis=1) + (
        dense @ d["dense_linear"]["kernel"] + d["dense_linear"]["bias"]).reshape(-1)
    x = jnp.concatenate([emb.reshape(emb.shape[0], -1), dense], axis=-1)
    return first + fm2 + tower(d, x, num_tower_layers(d)) + d["bias"][0]


def loss_sum(params, batch):
    """Sum, not mean, of the masked per-example losses: micro-batches add."""
    per_example = bce_with_logits(logits(params, batch), batch["labels"])
    return jnp.sum(per_example * batch["mask"].astype(jnp.float32))


def adam_step(params, grads, mu, nu, t):
    """optax.adam's arithmetic written out: bias-corrected moments, epsilon
    outside the root. `t` counts from 1."""
    def one(p, g, m, v):
        m = B1 * m + (1.0 - B1) * g
        v = B2 * v + (1.0 - B2) * g * g
        m_hat = m / (1.0 - B1 ** t)
        v_hat = v / (1.0 - B2 ** t)
        return p - LEARNING_RATE * m_hat / (jnp.sqrt(v_hat) + EPS), m, v

    flat_p, tree = jax.tree_util.tree_flatten(params)
    out = [one(p, g, m, v) for p, g, m, v in zip(
        flat_p, tree.flatten_up_to(grads), tree.flatten_up_to(mu),
        tree.flatten_up_to(nu))]
    return tuple(tree.unflatten([o[i] for o in out]) for i in range(3))
