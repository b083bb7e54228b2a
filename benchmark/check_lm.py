"""The comparison that decides `correct` for a language-model trainer cell:
the program's first steps against the configuration's plain reference,
outside the window, at full width, every leaf and every expert.

From the program's own initial parameters, `steps` training steps on the
cell's first batches. Compared: the loss of every step; AdamW's first moment
of every parameter leaf (linear in the gradients, so every matmul's backward
shows there undamped), each expert's slice of the experts' leaves apart; the
parameter update of every leaf over the steps; and the routers' decisions, in
two ways. On the SAME input (the residual stream the program's router saw,
first step, same parameters) program and reference must agree almost to the
bit: that is what a bfloat16 router, renormalised weights or a missing slot
fail. End to end (the reference's own forward pass, which differs from the
program's by its bfloat16 matmuls upstream of the router) a pair that flips
at a near-tie is rounding: the share of agreeing pairs is required to be NEAR
one, and the reference then computes with the program's choice of experts,
so that the moments of an expert are not judged by a token it did not get.
The tolerances are the reference's `TOLERANCES`, with their reasons.

The program's state and the reference do not fit on one chip together: the
caller reads the program's results back (`read_program`), releases its state,
and only then lets the reference run (`compare`). The reference keeps its
AdamW moments on the host between steps for the same reason.
"""

from __future__ import annotations

import time

import numpy as np

EXPERT_LEAVES = ("w_gate", "w_up", "w_down")     # (layers, experts, ., .)


_CHUNK = 1 << 22     # elements of the one scratch buffer differences go through
_SCRATCH = np.empty(_CHUNK, np.float32)


def _sq_norm(a, b=None) -> float:
    """Squared L2 norm of a (or of a - b), float32 arrays of one shape.
    Differences go chunk by chunk through one reused buffer: a fresh array
    the size of a leaf (half a gigabyte) costs seconds of page faults, and
    the check compares 45 of them. BLAS's dot does the sums (threaded); NaN
    or inf anywhere comes out as such."""
    a = np.ravel(a)
    if b is None:
        return float(np.dot(a, a))
    b, total = np.ravel(b), 0.0
    for i in range(0, a.size, _CHUNK):
        d = np.subtract(a[i:i + _CHUNK], b[i:i + _CHUNK],
                        out=_SCRATCH[:min(_CHUNK, a.size - i)])
        total += float(np.dot(d, d))
    return total


def _rel_l2(got, want, base=None) -> float:
    """|got - want| / |want - base| (base: zero where not given)."""
    return float(np.sqrt(_sq_norm(got, want) / max(_sq_norm(want, base), 1e-60)))


def adam_moments(opt_state):
    """(mu, nu) of the `optax` Adam inside an optimizer state, however it is
    wrapped (inject_hyperparams, chain)."""
    nodes = [opt_state]
    while nodes:
        node = nodes.pop()
        if hasattr(node, "mu") and hasattr(node, "nu"):
            return node.mu, node.nu
        if isinstance(node, (tuple, list)):
            nodes.extend(node)
        elif hasattr(node, "inner_state"):
            nodes.append(node.inner_state)
    raise RuntimeError("no Adam moments (mu, nu) in the optimizer state")


def _host(tree):
    import jax

    return jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32), tree)


def chosen_mask(idx, num_experts: int) -> np.ndarray:
    """(L, N, k) expert ids -> (L, N, E) bool."""
    idx = np.asarray(idx)
    mask = np.zeros(idx.shape[:2] + (num_experts,), bool)
    np.put_along_axis(mask, idx, True, axis=-1)
    return mask


def routing_figures(idx, weights, chosen, probs) -> dict:
    """A program's decisions (idx, weights) (L, N, k) against a reference
    router's (chosen (L, N, E) bool, probs (L, N, E)): the share of the
    reference's pairs the program also chose, the median relative error of
    the weights of those pairs, and both pair counts."""
    idx, weights = np.asarray(idx), np.asarray(weights, np.float64)
    chosen, probs = np.asarray(chosen), np.asarray(probs, np.float64)
    both = np.take_along_axis(chosen, idx, axis=-1)          # (L, N, k)
    want = np.take_along_axis(probs, idx, axis=-1)
    rel = np.abs(weights - want)[both] / want[both]
    return {
        # a token's k experts are distinct, so the pairs in both are `both`
        "agreement": float(np.sum(both) / max(np.sum(chosen), 1)),
        "weight_rel_median": float(np.median(rel)) if rel.size else float("inf"),
        "pairs_program": int(idx.size),
        "pairs_reference": int(np.sum(chosen)),
    }


def _rel_l2_by_expert(got, want, base=None) -> np.ndarray:
    """`_rel_l2` of each expert's slice of a (layers, experts, ., .) leaf."""
    layers, experts = want.shape[:2]

    def sq(a, b, e):
        return sum(_sq_norm(a[l, e], None if b is None else b[l, e])
                   for l in range(layers))

    return np.array([np.sqrt(sq(got, want, e) / max(sq(want, base, e), 1e-60))
                     for e in range(experts)])


def compare(got: dict, want: dict, params0: dict, tolerances: dict) -> dict:
    """got: {"losses" (steps,), "mu" {leaf}, "params" {leaf}}; want: the same
    and "router_same_input" / "routing" (lists of `routing_figures`, one per
    compared step). Returns {"ok", "figures", "failures"}."""
    figures, failures = {}, []

    def hold(name, value, limit, at_least=False):
        figures[name] = value
        bad = not (value >= limit) if at_least else not (value <= limit)
        if bad:
            failures.append(f"{name} {value:.4g} {'<' if at_least else '>'} {limit:.4g}")

    losses, ref_losses = np.asarray(got["losses"], np.float64), np.asarray(want["losses"])
    hold("loss_rel", float(np.max(np.abs(losses - ref_losses) / np.abs(ref_losses))),
         tolerances["loss_rel"])
    same, end_to_end = want["router_same_input"], want["routing"]
    hold("router_same_input_agreement", min(r["agreement"] for r in same),
         tolerances["router_same_input_agreement_min"], at_least=True)
    hold("router_weight_rel_median", max(r["weight_rel_median"] for r in same),
         tolerances["router_weight_rel_median"])
    hold("routing_agreement", min(r["agreement"] for r in end_to_end),
         tolerances["routing_agreement_min"], at_least=True)
    for r in same + end_to_end:
        if r["pairs_program"] != r["pairs_reference"]:
            failures.append(f"the program routes {r['pairs_program']} pairs, "
                            f"the reference {r['pairs_reference']}")
            break

    def limit(kind, leaf):
        table = tolerances[kind]
        group = "experts" if leaf in EXPERT_LEAVES else leaf
        return table.get(group, table["default"])

    experts_compared = 0
    for leaf in sorted(params0):
        # the update's error is the parameters' (the starting point cancels)
        for kind, ours, theirs, base in (
                ("mu", got["mu"][leaf], want["mu"][leaf], None),
                ("update", got["params"][leaf], want["params"][leaf], params0[leaf])):
            if leaf in EXPERT_LEAVES:
                # every expert apart, the worst one held to the tolerance
                each = _rel_l2_by_expert(ours, theirs, base)
                experts_compared = max(experts_compared, each.size)
                figures[f"{kind}_rel_l2.{leaf}.median_expert"] = float(np.median(each))
                hold(f"{kind}_rel_l2.{leaf}.worst_expert", float(np.max(each)),
                     limit(f"{kind}_rel_l2", leaf))
            else:
                hold(f"{kind}_rel_l2.{leaf}", _rel_l2(ours, theirs, base),
                     limit(f"{kind}_rel_l2", leaf))
    if not np.all(np.isfinite(losses)):     # a non-finite leaf fails its figure
        failures.append("non-finite loss")
    figures.update(
        experts_compared=experts_compared, leaves_compared=len(params0),
        router_same_input=same, routing=end_to_end,
        losses_program=[float(x) for x in losses],
        losses_reference=[float(x) for x in ref_losses])
    return {"ok": not failures, "figures": figures, "failures": failures}


class LMStepCheck:
    """`before(state)` copies the starting point; the caller runs the
    program's steps on `self.batches`, ONE step a dispatch, and takes the
    program's routing before each (`routings`); `read_program(state, losses,
    routings)` brings its results to the host; after the caller has released
    the program's state, `compare()` runs the reference and compares."""

    def __init__(self, reference, model_params: dict, batches: list):
        """batches: host batches {"features" (B, T) int, "labels" (B, T) int,
        "mask" (B,)}, one per step."""
        self.ref = reference
        self.hp = reference.hyper(model_params)
        self.batches = batches
        self.params0 = None
        self.got = None

    def before(self, state):
        self.params0 = _host(state.params)

    def read_program(self, state, losses, routings):
        """routings: per step, the program's own (expert_idx, weights,
        router_input) on that step's batch from the parameters it had then."""
        mu, _ = adam_moments(state.opt_state)
        self.got = {
            "losses": np.asarray(losses, np.float64).reshape(-1),   # waits for the steps
            "mu": _host(mu), "params": _host(state.params),
            "routings": [tuple(np.asarray(a) for a in r) for r in routings]}

    def reference_steps(self) -> dict:
        """The reference's own trajectory from `params0`, its experts chosen
        as the program chose them: {"losses", "mu", "params", "routing" (the
        program's choice against the reference's own, per step),
        "router_same_input" (the program's router against the reference's on
        the residual stream the program's router saw; first step, where the
        parameters are the same to the bit)}. Moments rest on the host
        between steps."""
        import jax
        import jax.numpy as jnp

        ref, hp = self.ref, self.hp
        device = jax.local_devices()[0]
        grad = jax.jit(jax.value_and_grad(
            lambda p, b, chosen: ref.loss(p, b, hp, chosen), has_aux=True))
        routers_on = jax.jit(lambda p, x: ref.routers_on(p, x, hp))
        adamw = jax.jit(ref.adamw_step, donate_argnums=(0, 2, 3))
        zeros = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree))
        losses, routing, same = [], [], []
        with jax.default_matmul_precision("highest"), jax.default_device(device):
            params = jax.device_put(self.params0, device)
            mu = nu = None
            for i, batch in enumerate(self.batches):
                idx, weights, router_input = self.got["routings"][i]
                if i == 0:
                    same.append(routing_figures(
                        idx, weights, *jax.device_get(routers_on(params, router_input))))
                ref_batch = {"tokens": jnp.asarray(batch["features"], jnp.int32),
                             "labels": jnp.asarray(batch["labels"], jnp.int32),
                             "mask": jnp.asarray(batch["mask"], jnp.float32)}
                (value, own), grads = grad(
                    params, ref_batch, chosen_mask(idx, hp["num_experts"]))
                losses.append(float(value))
                routing.append(routing_figures(idx, weights, *jax.device_get(own)))
                del own
                if mu is None:
                    mu, nu = zeros(params), zeros(params)
                else:
                    mu, nu = jax.device_put((mu, nu), device)
                params, mu, nu = adamw(params, grads, mu, nu, jnp.float32(i + 1))
                del grads
                mu, nu = _host(mu), _host(nu)       # off the chip: the next
                # step's activations need the room
        return {"losses": np.asarray(losses), "mu": mu, "params": _host(params),
                "routing": routing, "router_same_input": same}

    def compare(self) -> dict:
        marks = [("start", time.monotonic())]
        want = self.reference_steps()
        marks.append(("reference_steps", time.monotonic()))
        verdict = compare(self.got, want, self.params0, self.ref.TOLERANCES)
        marks.append(("compared", time.monotonic()))
        verdict["figures"]["seconds"] = {
            b[0]: round(b[1] - a[1], 2) for a, b in zip(marks, marks[1:])}
        return verdict
