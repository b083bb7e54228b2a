"""The comparison that decides `correct` for a trainer cell: the program's
first steps against the configuration's plain reference, outside the window.

From the program's own initial state, `steps` training steps on the cell's
first batches. The reference holds only the table rows those batches touch,
compacted and re-indexed: a row no batch touches has a zero gradient and,
from Adam's zero state, does not move — which the check also holds the
program to, on a seeded sample of untouched rows. Compared: the loss of every
step; Adam's first moment of the touched rows (linear in the gradients, so
the placement of embedding gradients shows there undamped; the last column
apart from the latent ones); the change of the touched rows and of the dense
parameters over the steps. Tolerances and their reasons are in the
reference's file.
"""

from __future__ import annotations

import time

import numpy as np


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def get_path(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _without(tree, path):
    if len(path) == 1:
        return {k: v for k, v in tree.items() if k != path[0]}
    out = dict(tree)
    out[path[0]] = _without(tree[path[0]], path[1:])
    if not out[path[0]]:
        del out[path[0]]
    return out


def _numpy_tree(tree):
    import jax

    return jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32), tree)


class StepCheck:
    """Two halves around the program's own steps: `before(state)` copies what
    the reference starts from, `after(state, losses)` runs the reference and
    compares. The caller runs the program in between, on `self.batches`."""

    def __init__(self, reference, model_params, batches, seed, micro_batch=2048):
        """batches: list of host batches {"features": {"dense", "cat"},
        "labels", "mask"} at the global batch size."""
        self.ref = reference
        self.batches = batches
        self.micro_batch = micro_batch
        rows = reference.row_ids(
            model_params, np.stack([b["features"]["cat"] for b in batches]))
        self.touched = np.unique(rows)
        self.compact_ids = np.searchsorted(self.touched, rows).astype(np.int32)
        # How many rows are touched depends on the seed. Every array the
        # helper programs see is padded to the most there can be, one row per
        # id, so that their shapes — and the compile cache's keys — do not.
        self.capacity = int(rows.size)
        self.gather_ids = np.concatenate([
            self.touched, np.full(self.capacity - self.touched.size,
                                  self.touched[0], self.touched.dtype)])
        self._rng = np.random.default_rng(seed)
        self._before = None

    # -------------------------------------------------------------- #

    def _take(self, table, ids):
        import jax
        import jax.numpy as jnp

        return np.asarray(jax.jit(lambda t, i: jnp.take(t, i, axis=0))(
            table, jnp.asarray(ids, jnp.int32)))

    def _take_touched(self, table, padded=False):
        """The touched rows of `table`; `padded` keeps the filler rows (zeroed:
        no id points at them, so the reference never moves them)."""
        rows = np.array(self._take(table, self.gather_ids))    # writable
        if not padded:
            return rows[:self.touched.size]
        rows[self.touched.size:] = 0.0
        return rows

    def _table_leaves(self, state):
        """(table, Adam's mu of it, Adam's nu of it): the optimizer's leaves
        of the table's shape, in optax's order."""
        import jax

        table = get_path(state.params, self.ref.TABLE)
        slots = [x for x in jax.tree_util.tree_leaves(state.opt_state)
                 if getattr(x, "shape", None) == table.shape]
        if len(slots) != 2:
            raise RuntimeError(
                f"expected Adam's two slots of shape {table.shape} in the "
                f"optimizer state, found {len(slots)}")
        return table, slots[0], slots[1]

    def before(self, state):
        table, _, _ = self._table_leaves(state)
        total_rows = table.shape[0]
        sample = np.setdiff1d(
            self._rng.integers(0, total_rows, 16384), self.touched)
        untouched = np.resize(sample, 4096)         # a fixed shape here too
        self._before = {
            "rows": self._take_touched(table, padded=True),
            "dense": _numpy_tree(_without(state.params, self.ref.TABLE)),
            "untouched_ids": untouched,
            "untouched": self._take(table, untouched),
        }

    def after(self, state, losses) -> dict:
        """Runs the reference and returns {"ok", "figures", "failures"}."""
        import jax
        import jax.numpy as jnp

        marks = [("start", time.monotonic())]
        ref, b0 = self.ref, self._before
        table, mu, _ = self._table_leaves(state)
        losses = np.asarray(losses, np.float64).reshape(-1)     # waits for the steps
        marks.append(("program_steps_done", time.monotonic()))
        got = {
            "rows": self._take_touched(table),
            "mu": self._take_touched(mu),
            "dense": _numpy_tree(_without(state.params, ref.TABLE)),
            "untouched": self._take(table, b0["untouched_ids"]),
            "losses": losses,
        }
        marks.append(("program_read", time.monotonic()))
        device = jax.local_devices()[0]
        params = jax.device_put(
            {"rows": b0["rows"], "dense": b0["dense"]}, device)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        mu_ref, nu_ref = zeros, zeros
        batch_size = self.batches[0]["labels"].shape[0]
        micro = min(self.micro_batch, batch_size)
        if batch_size % micro:
            raise ValueError(f"micro-batch {micro} does not divide {batch_size}")

        def grads_and_loss(params, batch):
            """Loss and gradients of one batch, summed over micro-batches one
            after another (the reference's CIN holds a (B, H, 26, D) float32
            product per layer)."""
            split = jax.tree_util.tree_map(
                lambda x: x.reshape((batch_size // micro, micro) + x.shape[1:]),
                batch)

            def body(carry, mb):
                g_acc, l_acc = carry
                value, g = jax.value_and_grad(ref.loss_sum)(params, mb)
                return (jax.tree_util.tree_map(jnp.add, g_acc, g), l_acc + value), None

            (g, total), _ = jax.lax.scan(
                body, (jax.tree_util.tree_map(jnp.zeros_like, params),
                       jnp.float32(0.0)), split)
            count = jnp.maximum(jnp.sum(batch["mask"].astype(jnp.float32)), 1.0)
            return jax.tree_util.tree_map(lambda x: x / count, g), total / count

        @jax.jit
        def step(params, mu, nu, batch, t):
            g, loss = grads_and_loss(params, batch)
            params, mu, nu = ref.adam_step(params, g, mu, nu, t)
            return params, mu, nu, loss

        ref_losses = []
        with jax.default_matmul_precision("highest"), jax.default_device(device):
            for i, batch in enumerate(self.batches):
                ref_batch = {
                    "ids": self.compact_ids[i],
                    "dense": batch["features"]["dense"],
                    "labels": batch["labels"],
                    "mask": batch["mask"],
                }
                params, mu_ref, nu_ref, loss = step(
                    params, mu_ref, nu_ref, ref_batch, jnp.float32(i + 1))
                ref_losses.append(float(loss))
        marks.append(("reference_steps", time.monotonic()))
        n = self.touched.size
        rows0 = b0["rows"][:n]
        want = {
            "rows": np.asarray(params["rows"])[:n],
            "mu": np.asarray(mu_ref["rows"])[:n],
            "dense": _numpy_tree(params["dense"]),
        }
        ref_losses = np.asarray(ref_losses)

        def flat(tree):
            return np.concatenate([np.ravel(x) for x in jax.tree_util.tree_leaves(tree)])

        lin_got, lin_want = got["mu"][:, -1], want["mu"][:, -1]
        hit = np.abs(lin_want) > 0
        emb_err = np.linalg.norm(got["mu"][:, :-1] - want["mu"][:, :-1], axis=1)
        emb_norm = np.linalg.norm(want["mu"][:, :-1], axis=1)
        figures = {
            "loss_rel": float(np.max(
                np.abs(got["losses"] - ref_losses) / np.abs(ref_losses))),
            "mu_lin_rel_l2": _rel_l2(lin_got, lin_want),
            "mu_lin_rel_median": float(np.median(
                np.abs(lin_got[hit] - lin_want[hit]) / np.abs(lin_want[hit]))),
            "mu_emb_rel_l2": _rel_l2(got["mu"][:, :-1], want["mu"][:, :-1]),
            "mu_emb_rel_median": float(np.median(
                emb_err[emb_norm > 0] / emb_norm[emb_norm > 0])),
            "rows_update_rel_l2": _rel_l2(
                got["rows"] - rows0, want["rows"] - rows0),
            "dense_update_rel_l2": _rel_l2(
                flat(got["dense"]) - flat(b0["dense"]),
                flat(want["dense"]) - flat(b0["dense"])),
        }
        failures = [f"{k} {v:.3e} > {ref.TOLERANCES[k]:.1e}"
                    for k, v in figures.items() if not v <= ref.TOLERANCES[k]]
        if not np.array_equal(got["untouched"], b0["untouched"]):
            failures.append("rows that no batch touched have moved")
        if not (np.all(np.isfinite(got["losses"])) and np.all(np.isfinite(got["rows"]))):
            failures.append("non-finite loss or parameter")
        marks.append(("compared", time.monotonic()))
        figures.update(
            seconds={b[0]: round(b[1] - a[1], 2) for a, b in zip(marks, marks[1:])},
            touched_rows=int(self.touched.size),
            untouched_rows_checked=int(b0["untouched_ids"].size),
            losses_program=[float(x) for x in got["losses"]],
            losses_reference=[float(x) for x in ref_losses],
        )
        return {"ok": not failures, "figures": figures, "failures": failures}
