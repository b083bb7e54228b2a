"""The departures the Keye-VL-2.0 cell's check must catch and the precision
controls its limits are read against (`CONTROLS`: what the configuration
states float32, kept in bfloat16), each as a patch of the PROGRAM (the zoo
module and `ops/sparse_attention.py`), and a command that runs the cell's
check — the driver's own `program_check` — under each of them on the chip at
full width:

    chiprun --chips 1 --timeout 3300 -- python3 benchmark/rehearse/departures_keye_vl2.py \
        [--seed N] [--only name,name] [--seeds a,b,c] [--check_steps 2]

Every line it prints holds `correct: true|false`, the failures and every
figure of the comparison. The unpatched program must read true, every
departure and every control false. The CPU tests (`tests/test_keye_vl2.py`)
apply the same patches at the tiny preset. None of this is run by the
benchmark; nothing here is an option of the program.
"""

from __future__ import annotations

import contextlib
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402

_mellum = common.load_module("rehearse", "departures_mellum")
_inside, fresh_trainer, _rounded = _mellum._inside, _mellum.fresh_trainer, _mellum._rounded


def _sparse():
    from elasticdl_tpu.ops import sparse_attention
    return sparse_attention


def _keys(by: int):
    """A query keeps `index_topk + by` keys."""
    def patch(zoo, jnp, jax):
        plain = _sparse().select
        return [(_sparse(), "select", lambda q, k_index, w, k: plain(q, k_index, w, k + by))]
    return patch


def _future_keys_compete(zoo, jnp, jax):
    """The k largest are taken over the WHOLE row, future keys among them, and
    the causal mask applied afterwards: a query whose future keys score high
    keeps fewer than k (its own position always, so that no row is empty)."""
    sa = _sparse()
    plain = sa.select

    def select(q_index, k_index, w, k):
        _, _, counts = plain(q_index, k_index, w, k)
        t = q_index.shape[1]
        rows = sa._rows(t, sa.SCORE_ROWS)

        def block(args):
            q_rows, w_rows, first_row = args
            scores = sa._score_block(q_rows, k_index, w_rows)
            threshold = jax.lax.top_k(scores, min(k, t))[0][..., -1]
            position = first_row + jnp.arange(rows)
            keys = jnp.arange(t)[None, :]
            keep = ((keys <= position[:, None]) & (scores >= threshold[..., None])) \
                | (keys == position[:, None])
            return threshold, keep.astype(jnp.int8)

        threshold, keep = jax.lax.map(block, (
            sa._blocked(q_index, 1, rows), sa._blocked(w, 1, rows), jnp.arange(0, t, rows)))
        return sa._unblocked(threshold, 1), sa._unblocked(keep, 1), counts

    return [(sa, "select", select)]


def _scores(activation, dtype_name: str, weights=lambda w: w):
    """A block of the score plane (`sparse_attention._score_block`, which the
    plane and the index loss's backward are both made of) with another
    `activation` in place of the relu, the matmul's output and everything
    after it in `dtype_name`, or other head weights."""
    def patch(zoo, jnp, jax):
        dt = jnp.dtype(dtype_name)

        def score_block(q_rows, k_index, w_rows):
            s = jnp.einsum("brhd,bsd->bhrs", q_rows, k_index, preferred_element_type=dt)
            weighted = activation(jax, s) * jnp.moveaxis(weights(w_rows).astype(dt), 2, 1)[..., None]
            return jnp.sum(weighted, axis=1).astype(jnp.float32)

        return [(_sparse(), "_score_block", score_block)]
    return patch


_relu = lambda jax, s: jax.nn.relu(s)


def _index_loss_left_out(zoo, jnp, jax):
    return [(_sparse(), "index_kl", lambda q_index, k_index, w, *rest: 0.0 * jnp.sum(w))]


def _target_not_divided_by_heads(zoo, jnp, jax):
    """p̂ is the SUM of the heads' probabilities: exp(s − (lse − ln H)) = H·P."""
    plain = _sparse().index_kl
    return [(_sparse(), "index_kl", lambda qi, ki, w, q, k, lse, keep: plain(
        qi, ki, w, q, k, lse - math.log(q.shape[2]), keep))]


def _target_not_detached(zoo, jnp, jax):
    """The index loss differentiated as it is written, p̂ and all: q and k (and
    through the logsumexp the whole attention) receive gradient from it too —
    the rule's own value and gradient, plus a term that is zero in value and
    carries the loss's gradient with respect to q, k and lse."""
    sa = _sparse()
    plain = sa.index_kl

    def through_the_target(q_index, k_index, w, q, k, lse, keep):
        b, t = keep.shape[:2]
        rows = sa._rows(t, sa.KL_ROWS)

        @jax.checkpoint
        def block(q_index_rows, w_rows, q_rows, lse_rows, keep_rows):
            target, log_pi, kept = sa._target_and_log_pi(
                q_rows, k, lse_rows, keep_rows,
                sa._score_block(q_index_rows, k_index, w_rows))
            return jnp.sum(jax.scipy.special.xlogy(target, target)
                           - target * jnp.where(kept, log_pi, 0.0))

        return jnp.sum(jax.lax.map(lambda a: block(*a), sa._kl_blocks(
            q_index, w, q, lse, keep, rows))) / (t * b)

    def index_kl(q_index, k_index, w, q, k, lse, keep):
        extra = through_the_target(*map(jax.lax.stop_gradient, (q_index, k_index, w)),
                                   q, k, lse, keep)
        return plain(q_index, k_index, w, q, k, lse, keep) \
            + extra - jax.lax.stop_gradient(extra)

    return [(sa, "index_kl", index_kl)]


def _indexer_input_not_detached(zoo, jnp, jax):
    return [(zoo, "detached", lambda x: x)]


def _selection_redone_in_the_backward_pass(zoo, jnp, jax):
    """The layer recomputed under the flash kernels' policy alone: thresholds
    and `keep` are not kept, the backward pass searches again on recomputed
    scores."""
    from elasticdl_tpu.ops import pallas_attention
    return [(_sparse(), "KEEP_SELECTION", pallas_attention.KEEP_RESIDUALS)]


def _qk_norm_left_out(zoo, jnp, jax):
    """q and k go on as the projections left them (the norms' weights, ones at
    the seed, are skipped with them)."""
    return _inside(zoo, "attention", "rmsnorm", lambda plain, cfg: (
        lambda x, w, eps: plain(x, w, eps) if x.shape[-1] == cfg.hidden_size else x))


def _residual_stream_in_bfloat16(zoo, jnp, jax):
    def block(p, x, tables, cfg, selection=False):
        update, chosen = zoo.attention(p, x, tables, cfg)
        stats = {name: value for name, value in chosen.items() if name != "keep"}
        if selection:
            stats.update(keep=chosen["keep"], layer_input=x)
        x = _rounded(x + update, jax)
        y, routed = zoo.moe(p, x, cfg)
        return _rounded(x + y, jax), {**stats, **routed}

    return [(zoo, "block", block)]


# the nearest precision below the stated one, where the statement is float32
CONTROLS = {
    "index_scores_in_bfloat16": _scores(_relu, "bfloat16"),
    "residual_stream_in_bfloat16": _residual_stream_in_bfloat16,
}

DEPARTURES = {
    "one_key_short": _keys(-1),
    "one_key_long": _keys(+1),
    "future_keys_compete": _future_keys_compete,
    "no_relu": _scores(lambda jax, s: s, "float32"),
    "head_weights_left_out": _scores(
        _relu, "float32", lambda w: 0.0 * w + 1.0),
    "index_loss_left_out": _index_loss_left_out,
    "target_not_divided_by_heads": _target_not_divided_by_heads,
    "target_not_detached": _target_not_detached,
    "indexer_input_not_detached": _indexer_input_not_detached,
    "selection_redone_in_the_backward_pass": _selection_redone_in_the_backward_pass,
    "topk_weights_not_renormalised": _mellum._weights_not_renormalised,
    "qk_norm_left_out": _qk_norm_left_out,
}


@contextlib.contextmanager
def applied(name, zoo):
    """The program with departure `name` patched in (None: as it is)."""
    import jax
    import jax.numpy as jnp

    patches = {**DEPARTURES, **CONTROLS}[name](zoo, jnp, jax) if name else []
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    for obj, attr, new in patches:
        setattr(obj, attr, new)
    try:
        yield
    finally:
        for obj, attr, old in saved:
            setattr(obj, attr, old)


def main(argv=None) -> int:
    _mellum.DEPARTURES, _mellum.CONTROLS, _mellum.applied = DEPARTURES, CONTROLS, applied
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--workload" not in argv:
        argv += ["--workload", "keye-vl-2.0-30b-a3b.resident-16k"]
    return _mellum.main(argv)


if __name__ == "__main__":
    raise SystemExit(main())
