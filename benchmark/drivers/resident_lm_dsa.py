"""Driver `resident_lm_dsa`: `drivers/resident_lm_stateless.py`'s run for a
model whose attention SELECTS its keys (a learned indexer and the K best keys
of a query's prefix). The run itself — sequences from the seed, the check, the
resident window, the trace, the counters — is that driver's, loaded from it;
what differs is handed to it:

- the check. Top-k is discontinuous, so the reference's loss and gradients are
  computed ON THE PROGRAM'S SELECTIONS — the `keep` planes of the check steps
  (`zoo.selections`), handed over as the routing (`chosen`) is — and the
  indexers' decisions are compared on their own, layer by layer and step by
  step, on the SAME input (the residual stream the program's layer started
  from): the relative error of the program's score plane (`zoo.index_plane`
  against `reference.index_plane`), the share of each row's selected keys on
  which program and reference (`reference.own_selection`: `lax.top_k`) agree,
  and the disagreements that lie further from the row's threshold than the
  scores' own error reaches. The terms of the loss are the three the step
  reports by name (`loss_ce`, `loss_balance`, `loss_index`), each held to a
  limit of its own; no `loss_aux` is derived.
- the kernels' names the trace is reduced by (`flash_attention_sel_*`).

Merging the five LM drivers is ROADMAP B0's.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import check_lm, common

# a copy of the module of this driver's own: what is set on it below reaches
# no other driver
_st = common.load_module("drivers", "resident_lm_stateless")
_share, _lm, _resident = _st._share, _st._lm, _st._resident

KERNEL_PREFIXES = ("flash_attention_sel", "flash_attention")
# what `zoo.index_plane` reads of a layer's parameters
_PLANE_KEYS = ("attn_norm", "index_wq", "index_wk", "index_k_scale", "index_k_bias",
               "index_w")


def selection_figures(scores, ours, kept, own, k: int):
    """A program's score plane `scores` and selection `kept` (B, T, T) against
    a reference's plane `ours` and selection `own` on the same input, as
    device scalars:
    `score_rel` (relative L2 error over the causal pairs), `agreement_min` /
    `agreement_mean` (over rows: the share of the row's min(t + 1, k) selected
    keys that `reference.own_selection` selects too), `outside_error` (pairs on
    which the two selections differ although the reference's score lies
    further from the row's threshold than twice the row's largest score
    error), `pairs_program`, `pairs_reference`."""
    import jax.numpy as jnp

    t = ours.shape[-1]
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    err = jnp.where(causal, jnp.abs(scores - ours), 0.0)
    score_rel = jnp.sqrt(jnp.sum(jnp.square(err))
                         / jnp.sum(jnp.square(jnp.where(causal, ours, 0.0))))
    count = jnp.minimum(jnp.arange(t) + 1, k)
    agreement = jnp.sum(kept & own, axis=-1) / count
    threshold = jnp.min(jnp.where(own, ours, jnp.inf), axis=-1, keepdims=True)
    reach = 2.0 * jnp.max(err, axis=-1, keepdims=True)
    outside = (kept != own) & (jnp.abs(ours - threshold) > reach)
    return {"score_rel": score_rel, "agreement_min": jnp.min(agreement),
            "agreement_mean": jnp.mean(agreement), "outside_error": jnp.sum(outside),
            "pairs_program": jnp.sum(kept), "pairs_reference": jnp.sum(own)}


class DsaStepCheck(_st.StatelessStepCheck):
    """`StatelessStepCheck` with the program's selections: the reference's
    steps are computed on them, and the indexers' decisions are compared on
    their own."""

    def read_program(self, state, metrics, routings, selections):
        """selections: per step (layer inputs (L, B, T, C), thresholds, keep
        (L, B, T, T) int8, the program's score planes [L x (B, T, T)])."""
        check_lm.LMStepCheck.read_program(
            self, state, np.concatenate([m["loss"] for m in metrics]), routings)
        self.got["terms"] = {
            name: np.concatenate([np.asarray(m[name], np.float64) for m in metrics])
            for name in metrics[0] if name != "loss"}
        self.got["selections"] = selections

    def reference_steps(self) -> dict:
        import jax
        import jax.numpy as jnp

        ref, hp = self.ref, self.hp
        device = jax.local_devices()[0]

        def total_and_rest(p, b, chosen, keep):
            total, terms, own = ref.loss_terms(p, b, hp, chosen, keep)
            return total, (terms, own)

        grad = jax.jit(jax.value_and_grad(total_and_rest, has_aux=True))
        routers_on = jax.jit(lambda p, x: ref.routers_on(p, x, hp))

        def figures_of(p, x, scores, keep):
            ours = ref.index_plane(p, x, hp)
            return selection_figures(scores, ours, keep != 0,
                                     ref.own_selection(ours, hp["index_topk"]),
                                     hp["index_topk"])

        figures = jax.jit(figures_of)
        adamw = jax.jit(lambda p, g, m, v, t: ref.adamw_step(p, g, m, v, t, hp["adamw"]),
                        donate_argnums=(0, 2, 3))
        zeros = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree))
        losses, terms_all, routing, same, selection = [], [], [], [], []
        with jax.default_matmul_precision("highest"), jax.default_device(device):
            params = jax.device_put(self.params0, device)
            mu = nu = None
            for i, batch in enumerate(self.batches):
                idx, weights, router_input = self.got["routings"][i]
                layer_inputs, _, keep, planes = self.got["selections"][i]
                same.append(check_lm.routing_figures(
                    idx, weights, *jax.device_get(routers_on(params, router_input))))
                selection.append([
                    {name: float(v) for name, v in jax.device_get(figures(
                        {key: params[key][layer] for key in _PLANE_KEYS},
                        layer_inputs[layer], planes[layer], keep[layer])).items()}
                    for layer in range(hp["num_hidden_layers"])])
                ref_batch = {"tokens": jnp.asarray(batch["features"], jnp.int32),
                             "labels": jnp.asarray(batch["labels"], jnp.int32),
                             "mask": jnp.asarray(batch["mask"], jnp.float32)}
                chosen = check_lm.chosen_mask(idx, hp["num_experts"])
                (value, (terms, own)), grads = grad(params, ref_batch, chosen,
                                                    jnp.asarray(keep != 0))
                losses.append(float(value))
                terms_all.append({k: float(v) for k, v in terms.items()})
                routing.append(check_lm.routing_figures(idx, weights, *jax.device_get(own)))
                del own
                if mu is None:
                    mu, nu = zeros(params), zeros(params)
                else:
                    mu, nu = jax.device_put((mu, nu), device)
                params, mu, nu = adamw(params, grads, mu, nu, jnp.float32(i + 1))
                del grads
                mu, nu = check_lm._host(mu), check_lm._host(nu)
        self.want_terms = {k: np.asarray([t[k] for t in terms_all]) for k in terms_all[0]}
        self.selection = selection
        return {"losses": np.asarray(losses), "mu": mu, "params": check_lm._host(params),
                "routing": routing, "router_same_input": same}

    def compare(self) -> dict:
        verdict = super().compare()
        tolerances, layers = self.ref.TOLERANCES, [f for step in self.selection for f in step]
        figures = {
            "index_score_rel": max(f["score_rel"] for f in layers),
            "selection_agreement": min(f["agreement_min"] for f in layers),
            "selection_agreement_mean": min(f["agreement_mean"] for f in layers),
            "selection_outside_error": sum(f["outside_error"] for f in layers),
            "selection_by_step_and_layer": self.selection}
        for name, limit, at_least in (
                ("index_score_rel", tolerances["index_score_rel"], False),
                ("selection_agreement", tolerances["selection_agreement_min"], True),
                ("selection_agreement_mean", tolerances["selection_agreement_mean_min"], True),
                ("selection_outside_error", tolerances["selection_outside_error_max"], False)):
            value = figures[name]
            if not (value >= limit if at_least else value <= limit):
                verdict["failures"].append(
                    f"{name} {value:.4g} {'<' if at_least else '>'} {limit:.4g}")
        if any(f["pairs_program"] != f["pairs_reference"] for f in layers):
            verdict["failures"].append("the program selects another number of (query, key) "
                                       "pairs than the reference")
        verdict["figures"].update(figures)
        verdict["ok"] = not verdict["failures"]
        return verdict


def program_check(trainer, spec, mesh, zoo, reference, model_params, check_batches,
                  fresh_state, say, assignments=None) -> dict:
    """The cell's check: the program's steps on `check_batches`, one step a
    dispatch, with the routing, the selections and the score planes of each
    step from its own state, read back; its state released; the reference's
    steps; the comparison. Returns `compare()`'s verdict."""
    import jax

    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    cfg = spec.model.cfg
    assignments = assignments or _st._assignments(zoo, spec)
    selections = jax.jit(lambda params, toks: zoo.selections(params, toks, cfg))
    plane = jax.jit(lambda p, x: zoo.index_plane(p, x, cfg))
    t = time.monotonic()
    state = fresh_state()
    checker = DsaStepCheck(reference, model_params, check_batches)
    checker.before(state)
    metrics, routings, selected = [], [], []
    for step_batch in check_batches:
        routings.append(jax.device_get(assignments(state.params, step_batch["features"])))
        layer_inputs, thresholds, keep = jax.device_get(
            selections(state.params, step_batch["features"]))
        planes = [jax.device_get(plane({key: state.params[key][layer] for key in _PLANE_KEYS},
                                       layer_inputs[layer]))
                  for layer in range(cfg.num_hidden_layers)]
        selected.append((layer_inputs, thresholds, keep, planes))
        state, m = trainer.train_many(state, shard_batch_stack(
            mesh, [step_batch], spec.batch_partition))
        metrics.append(m)
    checker.read_program(state, jax.device_get(metrics), routings, selected)
    del state, m            # the reference needs the chip's memory
    say(f"check: the program's {len(check_batches)} steps read back at "
        f"{time.monotonic() - t:.1f} s")
    verdict = checker.compare()
    say(f"check against the reference in {time.monotonic() - t:.1f} s: "
        f"{verdict['figures']}")
    for failure in verdict["failures"]:
        say(f"CHECK FAILED: {failure}")
    return verdict


_st.program_check = program_check
_st.KERNEL_PREFIXES = KERNEL_PREFIXES
run = _st.run
