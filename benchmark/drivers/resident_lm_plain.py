"""Driver `resident_lm_plain`: a language model WITHOUT routers and WITHOUT
exits — a dense stack entered once, one head, one cross entropy — as its
device step alone, the input path bypassed. The run is
`drivers/resident_lm_dense.py`'s, line for line (its `run` is called as it
is); what differs is the check, which has no exit distribution to hold:
`program_check` here stands where the dense driver's stands, and the kernels
whose device time is summed by name are the flash kernels' and the selective
scan's. Everything model-specific comes from the configuration's own modules,
as there:

- its reference (`reference/<model>.py`): `hyper`, `loss(params, batch, hp) ->
  (total, terms)`, `adamw_step`, `TOLERANCES`;
- its shape functions (`flops/<model>.py`): `SCOPES` and ONE `shape(
  model_params, batch, seq_len)` dict;
- the traffic file names the rehearsal's tiny sizes (`rehearse`).

The check (`PlainStepCheck`, `DenseStepCheck`'s form): the program's own
`check_steps` steps — the timed path's jitted step, one step a dispatch —
against the reference's from the same seeded state: `loss` and every term the
zoo's `loss` returns beside it, each to a limit of its own
(`TOLERANCES["<term>_rel"]`); AdamW's first moment and the parameters' update
of every leaf after the last step (`mu_rel_l2`, `update_rel_l2`). The
program's state is released while the reference runs, and the seconds of the
reference's steps are taken out of `setup_s`: set-up is the program's.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import check_lm, common

_dense = common.load_module("drivers", "resident_lm_dense")
_lm, _resident, _share = _dense._lm, _dense._resident, _dense._share

KERNEL_PREFIXES = ("flash_attention", "selective_scan")


class PlainStepCheck(_dense.DenseStepCheck):
    """`DenseStepCheck` without the exit distribution: `before(state)` copies
    the starting point; the caller runs the program's steps on `self.batches`,
    ONE step a dispatch; `read_program(state, metrics)` brings its results to
    the host; after the caller has released the program's state, `compare()`
    runs the reference and compares."""

    def read_program(self, state, metrics):
        """metrics: the step metrics of each compared step ({name: (1,)})."""
        mu, _ = check_lm.adam_moments(state.opt_state)
        self.got = {
            "terms": {name: np.concatenate([np.asarray(m[name], np.float64).reshape(-1)
                                            for m in metrics]) for name in metrics[0]},
            "mu": check_lm._host(mu), "params": check_lm._host(state.params)}

    def reference_steps(self) -> dict:
        """The reference's own trajectory from `params0`; moments rest on the
        host between steps."""
        import jax
        import jax.numpy as jnp

        ref, hp = self.ref, self.hp
        device = jax.local_devices()[0]
        grad = jax.jit(jax.value_and_grad(lambda p, b: ref.loss(p, b, hp), has_aux=True))
        adamw = jax.jit(lambda p, g, m, v, t: ref.adamw_step(p, g, m, v, t, hp["adamw"]),
                        donate_argnums=(0, 2, 3))
        zeros = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree))
        terms_all = []
        with jax.default_matmul_precision("highest"), jax.default_device(device):
            params = jax.device_put(self.params0, device)
            mu = nu = None
            for i, batch in enumerate(self.batches):
                ref_batch = {"tokens": jnp.asarray(batch["features"], jnp.int32),
                             "labels": jnp.asarray(batch["labels"], jnp.int32),
                             "mask": jnp.asarray(batch["mask"], jnp.float32)}
                (value, terms), grads = grad(params, ref_batch)
                terms_all.append({"loss": float(value),
                                  **{k: float(v) for k, v in terms.items()}})
                if mu is None:
                    mu, nu = zeros(params), zeros(params)
                else:
                    mu, nu = jax.device_put((mu, nu), device)
                params, mu, nu = adamw(params, grads, mu, nu, jnp.float32(i + 1))
                del grads
                mu, nu = check_lm._host(mu), check_lm._host(nu)
        return {"terms": {k: np.asarray([t[k] for t in terms_all]) for k in terms_all[0]},
                "mu": mu, "params": check_lm._host(params)}

    def compare(self) -> dict:
        marks = [("start", time.monotonic())]
        want = self.reference_steps()
        marks.append(("reference_steps", time.monotonic()))
        tolerances, figures, failures = self.ref.TOLERANCES, {}, []

        def hold(name, value, limit):
            figures[name] = value
            if not value <= limit:
                failures.append(f"{name} {value:.4g} > {limit:.4g}")

        for name, got in sorted(self.got["terms"].items()):
            ours = want["terms"][name]
            hold(f"{name}_rel",
                 float(np.max(np.abs(got - ours) / np.maximum(np.abs(ours), 1e-30))),
                 tolerances[f"{name}_rel"])
            figures[f"{name}_program"] = [float(x) for x in got]
            figures[f"{name}_reference"] = [float(x) for x in ours]
            if not np.all(np.isfinite(got)):
                failures.append(f"non-finite {name}")
        for leaf in sorted(self.params0):
            # the update's error is the parameters' (the starting point cancels)
            for kind, ours, theirs, base in (
                    ("mu_rel_l2", self.got["mu"][leaf], want["mu"][leaf], None),
                    ("update_rel_l2", self.got["params"][leaf], want["params"][leaf],
                     self.params0[leaf])):
                table = tolerances[kind]
                hold(f"{kind}.{leaf}", check_lm._rel_l2(ours, theirs, base),
                     table.get(leaf, table["default"]))
        figures["leaves_compared"] = len(self.params0)
        marks.append(("compared", time.monotonic()))
        figures["seconds"] = {b[0]: round(b[1] - a[1], 2) for a, b in zip(marks, marks[1:])}
        return {"ok": not failures, "figures": figures, "failures": failures}


def program_check(trainer, spec, mesh, zoo, reference, model_params, check_batches,
                  fresh_state, say) -> dict:
    """The cell's check: the program's steps on `check_batches`, one step a
    dispatch, read back; its state released; the reference's steps; the
    comparison. Returns `compare()`'s verdict. (The signature is every LM
    driver's. `zoo` is what the departures patch; nothing here reads it.)"""
    import jax

    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    del zoo
    t = time.monotonic()
    state = fresh_state()
    checker = PlainStepCheck(reference, model_params, check_batches)
    checker.before(state)
    metrics = []
    for step_batch in check_batches:
        state, m = trainer.train_many(state, shard_batch_stack(
            mesh, [step_batch], spec.batch_partition))
        metrics.append(m)
    checker.read_program(state, jax.device_get(metrics))
    del state, m            # the reference needs the chip's memory
    say(f"check: the program's {len(check_batches)} steps read back at "
        f"{time.monotonic() - t:.1f} s")
    verdict = checker.compare()
    say(f"check against the reference in {time.monotonic() - t:.1f} s: "
        f"{verdict['figures']}")
    for failure in verdict["failures"]:
        say(f"CHECK FAILED: {failure}")
    return verdict


# the dense driver's run with this check and these kernels' names in it: the
# module is this driver's own copy (`common.load_module` makes one a call)
_dense.program_check = program_check
_dense.KERNEL_PREFIXES = KERNEL_PREFIXES
run = _dense.run
