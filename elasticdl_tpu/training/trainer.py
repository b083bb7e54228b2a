"""The jitted training engine.

This replaces the reference's entire hot path — TF2-eager forward/backward on
the worker plus server-side optimizer application on the parameter server
(reference: elasticdl/python/worker/worker.py `training_process_eagerly`,
elasticdl/pkg/ps/optimizer.go) — with ONE `jax.jit`-compiled XLA program:
forward, loss, backward, `optax` update, all fused on-device.

Parallelism comes from the mesh, not from RPCs:
- the batch is sharded over the `data` axis, so the mean-loss gradient is a
  `psum` XLA inserts over ICI (this *is* the reference's allreduce mode),
- params carry flax partitioning metadata; anything unannotated is replicated,
  annotated tensors (embedding tables) are sharded — this *is* the reference's
  parameter-server placement, minus the per-step gRPC round-trips.

Model state is donated each step, so params update in place in HBM.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh

from elasticdl_tpu.common.log_utils import default_logger
from elasticdl_tpu.observability import profile as profile_lib
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.training import compile_cache as cc
from elasticdl_tpu.training.model_spec import ModelSpec
from elasticdl_tpu.training import metrics as metrics_lib

logger = default_logger(__name__)


class TrainState(struct.PyTreeNode):
    """Functional training state: a pytree living (sharded) in device HBM.

    The reference kept `step` as the PS "model version" used for staleness
    control (reference: elasticdl/pkg/ps/parameter.go); here there is no
    staleness — `step` is just the global step counter, and doubles as the
    model version reported to the master.
    """

    step: jnp.ndarray
    params: Any
    opt_state: Any
    extra_vars: Any          # non-param collections, e.g. batch_stats
    rng: jax.Array

    @property
    def model_version(self) -> int:
        return int(jax.device_get(self.step))


def _split_batch(batch: Dict[str, Any]):
    features = batch["features"]
    labels = batch.get("labels")
    mask = batch.get("mask")
    return features, labels, mask


def _masked_mean(value, mask):
    """A per-example vector's masked mean; a scalar as it is."""
    value = jnp.asarray(value)
    if value.ndim == 0:
        return value
    value = value.reshape(-1).astype(jnp.float32)
    if mask is None:
        return jnp.mean(value)
    m = jnp.asarray(mask, jnp.float32).reshape(-1)
    return jnp.sum(value * m) / jnp.maximum(jnp.sum(m), 1.0)


def _loss_terms(value):
    """A user loss's value as (what is minimised, the terms reported beside
    it): a loss may return a dict whose `loss` entry is minimised and whose
    other entries (the terms of a sum, say) only ride along."""
    if isinstance(value, dict):
        return value["loss"], {k: v for k, v in value.items() if k != "loss"}
    return value, {}


def _masked_scalar_loss(loss_fn, labels, outputs, mask):
    """Apply the user loss; accept per-example vectors (masked mean) or
    scalars (used as-is)."""
    return _masked_mean(_loss_terms(loss_fn(labels, outputs))[0], mask)


def _aux_loss(new_vars, weight: float):
    """weight * sum of everything sown into the "losses" collection (e.g.
    api.layers.MoE's Switch load-balance penalty). Added INSIDE the
    differentiated loss so auxiliaries regularize training; 0-weight jobs
    pay nothing (static branch)."""
    if not weight:
        return jnp.float32(0.0)
    leaves = jax.tree_util.tree_leaves(new_vars.get("losses", {}))
    if not leaves:
        return jnp.float32(0.0)
    return jnp.float32(weight) * sum(
        jnp.sum(jnp.asarray(l, jnp.float32)) for l in leaves)


def _aux_terms(new_vars, weight: float, names: Dict[str, str]) -> Dict[str, Any]:
    """{reported name: weight * the sown term of that name}, for the terms a
    zoo module asks to see apart (`ModelSpec.aux_loss_terms`)."""
    sown = new_vars.get("losses", {})
    return {reported: jnp.float32(weight) * sum(
                jnp.sum(jnp.asarray(l, jnp.float32))
                for l in jax.tree_util.tree_leaves(sown[name]))
            for name, reported in names.items() if name in sown}


_warned_scalar_accum = False


def _warn_scalar_loss_with_accum() -> None:
    """ADVICE r4: a user loss returning a pre-reduced SCALAR under
    grad_accum weighs micro-batches equally, which diverges from the
    full-batch masked mean when padding is uneven across micro-batches.
    Every zoo loss is per-example so this never fires in-tree; warn once
    so a user scalar loss over masked data isn't silently different."""
    global _warned_scalar_accum
    if not _warned_scalar_accum:
        _warned_scalar_accum = True
        logger.warning(
            "grad_accum_steps > 1 with a loss that returns a pre-reduced "
            "scalar: micro-batches are weighed equally, which differs from "
            "the unaccumulated step when padding/mask density varies across "
            "micro-batches. Return a per-example loss vector for exact "
            "full-batch-equivalent gradients."
        )


def _accumulated_grads(forward, loss_fn, state, features, labels, mask,
                       step_rng, accum, aux_weight: float = 0.0):
    """Gradient accumulation: split the batch into `accum` micro-batches
    along the leading dim, `lax.scan` forward+backward over them holding
    ONE micro-batch of activations live at a time, and return grads exactly
    equal to the full-batch step's (so K is a pure HBM knob, not a
    semantics change).

    Exactness: per-example (vector) losses accumulate masked SUM and count,
    dividing once at the end — identical to the full batch's weighted mean
    even with padded rows concentrated in one micro-batch. A user loss that
    returns a SCALAR is assumed to be a mean over its micro-batch (true of
    every zoo loss); micro-batches then weigh equally. The exactness claim
    is scoped to aux_weight=0: sown auxiliary losses (MoE balance) are
    batch-DEPENDENT statistics, so per-micro aux (micro-sized capacity,
    per-micro frac/mean_prob) legitimately differs from the full-batch
    aux — the example-count weighting below is the accumulation-consistent
    choice, not an equality guarantee. BatchNorm-style
    extra_vars thread through the scan (last micro-batch wins, matching K
    sequential steps); dropout draws per-micro-batch folds of the step
    rng."""

    def to_micro(x):
        b = x.shape[0]
        if b % accum:
            raise ValueError(
                f"grad_accum={accum} must divide the batch size {b}")
        # STRIDED split (row j*K+k -> micro k, slot j), NOT a contiguous
        # reshape: the batch dim arrives sharded P('data') with each device
        # holding a contiguous row block, and a contiguous split would put
        # each micro-batch on only N/K devices — GSPMD then reshards the
        # whole batch (all-to-all) every step. The strided mapping keeps
        # every device's rows local in every micro-batch, and grads are
        # masked-sum/divide-once weighted so the grouping is semantically
        # irrelevant.
        return x.reshape((b // accum, accum) + x.shape[1:]).swapaxes(0, 1)

    # mask may be None: pytrees treat None as structure, so the 3-tuple
    # shape survives the scan with m arriving as None
    micro = jax.tree_util.tree_map(to_micro, (features, labels, mask))

    def body(carry, mb):
        g_acc, loss_acc, cnt_acc, vars_c, i = carry
        f, l, m = mb
        rng = jax.random.fold_in(step_rng, i)

        def sum_loss(params):
            variables = {"params": params, **vars_c}
            outputs, new_vars = forward(variables, f, rng)
            value = jnp.asarray(_loss_terms(loss_fn(l, outputs))[0])
            if value.ndim == 0:
                # pre-reduced scalar: weigh micro-batches equally (ndim is
                # static, so this warning fires once at trace time)
                _warn_scalar_loss_with_accum()
                return value + _aux_loss(new_vars, aux_weight), (
                    jnp.float32(1.0), new_vars)
            v = value.reshape(-1).astype(jnp.float32)
            mm = (jnp.asarray(m, jnp.float32).reshape(-1) if m is not None
                  else jnp.ones_like(v))
            cnt = jnp.sum(mm)
            # aux scaled by this micro-batch's example count so the final
            # divide-once yields the example-weighted mean of the PER-MICRO
            # aux (see the exactness scoping in the docstring: batch-
            # dependent aux statistics cannot equal the full-batch value)
            return jnp.sum(v * mm) + _aux_loss(new_vars, aux_weight) * cnt, (
                cnt, new_vars)

        (s, (cnt, new_vars)), g = jax.value_and_grad(
            sum_loss, has_aux=True)(state.params)
        g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
        return (g_acc, loss_acc + s, cnt_acc + cnt, new_vars, i + 1), None

    zeros = jax.tree_util.tree_map(jnp.zeros_like, state.params)
    (g_sum, loss_sum, cnt, new_vars, _), _ = jax.lax.scan(
        body,
        (zeros, jnp.float32(0.0), jnp.float32(0.0), state.extra_vars,
         jnp.int32(0)),
        micro,
    )
    denom = jnp.maximum(cnt, 1.0)
    grads = jax.tree_util.tree_map(lambda g: g / denom, g_sum)
    return loss_sum / denom, new_vars, grads


# identifies the XLA program a (state, batch) pair lowers to; shared with
# the executable cache so AOT keys and cost-cache keys agree
_aval_signature = cc.aval_signature


def resolve_remat_policy(name: str):
    """Map a config-level policy name to a jax.checkpoint policy. "" (full
    remat: save nothing the policy engine controls) returns None. The menu
    is the standard HBM/FLOPs trade for long-context training on TPU:
    `dots` keeps MXU outputs and recomputes the (cheap, VPU) elementwise
    chain — the usual best trade; `dots_no_batch` additionally drops
    batch-dim matmul outputs (attention scores) — bigger savings, more
    recompute; `nothing` recomputes everything — minimum HBM."""
    if not name:
        return None
    policies = {
        "dots": jax.checkpoint_policies.dots_saveable,
        "dots_no_batch":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "nothing": jax.checkpoint_policies.nothing_saveable,
    }
    if name not in policies:
        raise ValueError(
            f"unknown remat policy {name!r}; choose from "
            f"{sorted(policies)} or '' for full remat"
        )
    return policies[name]


class Trainer:
    """Builds and runs the jitted train/eval/predict steps for one ModelSpec
    on one Mesh."""

    def __init__(
        self,
        spec: ModelSpec,
        mesh: Mesh,
        remat: bool = False,
        remat_policy: str = "",
        grad_accum: int = 1,
        seed: int = 0,
        cache_token: str = "",
        cache: "cc.CompileCache" = None,
    ):
        self.spec = spec
        self.mesh = mesh
        # Executable-cache identity (rescale fast path): job entrypoints
        # pass a config-derived token so pre/post-resize trainers (and the
        # speculative compiler's neighbor trainers) share programs through
        # the process-global cache. Ad-hoc trainers (no token) get a
        # PRIVATE cache instead: entries — and the compiled executables
        # plus closed-over models they pin — die with the trainer, exactly
        # the pre-cache lifetime (a global insert would pin every
        # short-lived trainer's programs until LRU pressure evicts them).
        self.cache_token = cache_token or cc.instance_token()
        if cache is not None:
            self._cache = cache
        elif cache_token:
            self._cache = cc.global_cache()
        else:
            self._cache = cc.CompileCache()
        # AOT executables pinned per kind: (aval signature, executable or
        # None, cache AOT generation); resolved lazily per call kind
        self._pinned_exe: Dict[str, Tuple[Any, Any, int]] = {}
        # the (kind, aval signature) pairs `_dispatch` has sent to the jitted
        # path: the first of each compiles, under a `compile` span
        self._dispatched: set = set()
        profile_lib.install_compile_ledger()
        # a named policy implies remat on; "" + remat=True is full remat.
        # Resolved HERE so a bad name fails at construction, not at the
        # first train-step build after the job is already running.
        self.remat = remat or bool(remat_policy)
        self.remat_policy = remat_policy
        self._resolved_remat_policy = resolve_remat_policy(remat_policy)
        if grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
        self.grad_accum = int(grad_accum)
        self.seed = seed
        self.metrics: Dict[str, metrics_lib.Metric] = (
            dict(spec.eval_metrics_fn()) if spec.eval_metrics_fn else {}
        )
        self._train_step = None
        # AOT cost-analysis results keyed by the (state, batch) aval
        # signature — a second train_step_cost call with a different batch
        # shape is a different XLA program and must not reuse the first
        # result (round-5 advisor)
        self._cost_cache: Dict[Any, Dict[str, float]] = {}
        self._train_many = None
        self._eval_step = None
        self._eval_many = None
        self._predict_step = None
        self._predict_many = None

    # ------------------------------------------------------------------ #
    # Executable cache plumbing (rescale fast path)

    def _program_key(self, kind: str) -> Tuple:
        """Identity of one step PROGRAM: config-derived token + mesh
        fingerprint + every trainer knob that changes the trace. No world
        version, no process identity — which is exactly what makes a
        re-formed world at the same shape a cache HIT."""
        return (
            self.cache_token,
            kind,
            cc.mesh_fingerprint(self.mesh),
            self.remat,
            self.remat_policy,
            self.grad_accum,
            float(self.spec.aux_loss_weight or 0.0),
        )

    def _ensure(self, attr: str, kind: str, build,
                speculative: bool = False) -> Any:
        """Resolve the jitted callable for `kind` through the shared
        executable cache, pinning it on the instance (one counted cache
        lookup per trainer per kind — a post-resize trainer that finds the
        previous generation's callable is the `recompile_hit_rate` hit;
        speculative resolutions count as speculative, not misses)."""
        fn = getattr(self, attr)
        if fn is None:
            fn = self._cache.get_or_build(
                self._program_key(kind), build, speculative=speculative)
            setattr(self, attr, fn)
        return fn

    def compile_stats(self) -> Dict[str, float]:
        """Hit/miss/speculative counters of the shared executable cache."""
        return self._cache.stats()

    def _dispatch(self, kind: str, jitted, *args):
        """Prefer a cache-resident AOT executable for these exact avals
        (the speculative compiler's output); fall back to the jitted
        callable. The common case — no AOT entry exists for this kind —
        pays ZERO per-step overhead: once a negative lookup is pinned (at
        a signature's SECOND dispatch: a kind's first signature is often
        not its last — a check's stack of 4 steps before a window's of 32 —
        and the one after it compiles too), the cache's AOT generation
        counter (bumped on every store_aot) is the only thing checked until
        a new executable could actually match.
        Known trade: an AOT entry stored for a shape OTHER than the pinned
        one, before any store bumps the generation again, can be shadowed
        by the negative pin — it then just runs the (correct) jitted path;
        and a new shape dispatched after a negative pin compiles under no
        `compile` span (the compile ledger counts it as `outside`).

        The first jitted dispatch of a (kind, aval signature) is where a
        program that nobody compiled ahead of time is traced, lowered and
        compiled, or loaded from the persistent cache: it runs under a
        `compile` span."""
        gen = self._cache.aot_generation
        pinned = self._pinned_exe.get(kind)
        if pinned is not None and pinned[2] == gen and pinned[1] is None:
            return jitted(*args)
        sig = cc.aval_signature(args)
        if pinned is None or pinned[0] != sig or pinned[2] != gen:
            exe = self._cache.peek(self._program_key(kind) + ("aot", sig))
            pinned = (sig, exe, gen)
            if exe is not None or (kind, sig) in self._dispatched:
                self._pinned_exe[kind] = pinned
        exe = pinned[1]
        if exe is not None:
            try:
                return exe(*args)
            except Exception:
                # input sharding/layout drifted from what the executable
                # was lowered with: drop to the jitted path (which
                # reshards) for good on this shape
                logger.warning(
                    "AOT executable for %s rejected its inputs; falling "
                    "back to the jitted path", kind, exc_info=True,
                )
                self._pinned_exe[kind] = (sig, None, gen)
        if (kind, sig) in self._dispatched:
            return jitted(*args)
        self._dispatched.add((kind, sig))
        with tracing.span("compile", program=kind, aot=False):
            return jitted(*args)

    def _aot_compile(self, attr: str, kind: str, build, args,
                     speculative: bool = False):
        """`.lower().compile()` the program for these exact (sharded) args
        and park the executable in the shared cache — which also feeds the
        persistent on-disk XLA cache when one is configured. Idempotent per
        aval signature."""
        fn = self._ensure(attr, kind, build, speculative=speculative)
        key = self._program_key(kind) + ("aot", cc.aval_signature(args))
        exe = self._cache.peek(key)
        if exe is not None:
            return exe
        # `edl.compile` in a device trace too: a compile inside a traced
        # window says so (the speculative compiler's thread shows on its own
        # line)
        with jax.set_mesh(self.mesh), \
                tracing.span("compile", program=kind, aot=True):
            exe = fn.lower(*args).compile()
        return self._cache.store_aot(key, exe, speculative=speculative)

    def _aot_batch(self, batch, abstract: bool):
        """Concrete callers get the real sharded batch; abstract callers
        (speculative compiles for worlds this process cannot execute on)
        get the ShapeDtypeStruct mirror — identical avals and shardings,
        zero data movement."""
        if abstract:
            return mesh_lib.abstract_batch(
                self.mesh, batch, self.spec.batch_partition)
        return mesh_lib.shard_batch(self.mesh, batch, self.spec.batch_partition)

    def aot_compile_train_step(self, state, batch, speculative: bool = False,
                               abstract: bool = False):
        return self._aot_compile(
            "_train_step", "train_step", self._build_train_step,
            (state, self._aot_batch(batch, abstract)), speculative=speculative,
        )

    def aot_compile_eval_step(self, state, batch, speculative: bool = False,
                              abstract: bool = False):
        return self._aot_compile(
            "_eval_step", "eval_step", self._build_eval_step,
            (state, self._aot_batch(batch, abstract), self.new_metric_states()),
            speculative=speculative,
        )

    def aot_compile_predict_step(self, state, batch, speculative: bool = False,
                                 abstract: bool = False):
        return self._aot_compile(
            "_predict_step", "predict_step", self._build_predict_step,
            (state, self._aot_batch(batch, abstract)), speculative=speculative,
        )

    def aot_compile_train_many(self, state, stacked_batch,
                               speculative: bool = False):
        """AOT twin for the scan-of-steps program (callers on the grouped
        dispatch path — steps_per_dispatch > 1 — hand a stacked batch built
        with shard_batch_stack / make_global_batch_stack)."""
        return self._aot_compile(
            "_train_many", "train_many", self._build_train_many,
            (state, stacked_batch), speculative=speculative,
        )

    # ------------------------------------------------------------------ #
    # State creation

    def init_state(self, example_batch: Dict[str, Any]) -> TrainState:
        """Initialize sharded TrainState from an example batch.

        Params annotated with flax partitioning metadata (nn.with_partitioning,
        as used by the sharded Embedding layer) get their annotated
        NamedSharding; everything else is replicated. The whole init runs under
        jit so large sharded tables are initialized shard-wise on their own
        devices, never materialized on one host — the analog of the reference
        PS initializing embedding rows server-side
        (reference: elasticdl/pkg/ps/embedding.go lazy init).
        """
        with tracing.span("start.state", model=self.spec.module_name):
            state = self._init_state(example_batch)
            # the span holds the device's part too: what is dispatched here
            # would else be waited for under whatever reads the state first
            jax.block_until_ready(state)
        n = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
        logger.info("Initialized model %s: %.3fM params", self.spec.module_name, n / 1e6)
        return state

    def _init_state(self, example_batch: Dict[str, Any]) -> TrainState:
        model, tx = self.spec.model, self.spec.optimizer
        features, _, _ = _split_batch(example_batch)
        root_key = jax.random.PRNGKey(self.seed)

        def _variables(rng, feats):
            return model.init({"params": rng, "dropout": rng}, feats, training=False)

        def build_create():
            # Derive shardings from flax partitioning metadata. Optimizer
            # slots (Adam mu/nu, …) must shard exactly like their params —
            # the PS slot tables of the reference (elasticdl/pkg/ps/
            # embedding.go Adam slot tables) sharded with the rows. optax
            # tree ops preserve nn.Partitioned boxes, so running tx.init on
            # the *boxed* abstract params yields boxed slots whose specs we
            # can read; GSPMD propagation alone leaves them replicated.
            def _abstract(rng, feats):
                variables = _variables(rng, feats)
                return variables, tx.init(variables["params"])

            abstract, abstract_opt = jax.eval_shape(_abstract, root_key, features)
            param_shardings = nn.get_sharding(abstract, self.mesh)
            opt_shardings = nn.get_sharding(abstract_opt, self.mesh)

            def _create(rng, feats):
                variables = nn.meta.unbox(_variables(rng, feats))
                variables = jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, variables, param_shardings
                )
                params = variables.pop("params")
                opt_state = jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint,
                    tx.init(params),
                    opt_shardings,
                )
                return TrainState(
                    step=jnp.zeros((), jnp.int32),
                    params=params,
                    opt_state=opt_state,
                    extra_vars=variables,
                    rng=rng,
                )

            return jax.jit(_create)

        with jax.set_mesh(self.mesh):
            # Cache-keyed like the step programs (a re-formed world at an
            # unchanged shape must not re-trace model init). The key carries
            # the example-feature avals because the derived shardings bake
            # the parameter shapes in; features are an ARGUMENT of the
            # jitted program (not a closure constant), so a cached program
            # re-run with a different example batch stays value-correct
            # even for data-dependent initializers.
            create = self._cache.get_or_build(
                self._program_key("init") + (cc.aval_signature(features),),
                build_create,
            )
            return create(root_key, features)

    def abstract_train_state(self, example_batch: Dict[str, Any]) -> TrainState:
        """Execution-free twin of `init_state`: the same TrainState pytree
        as ShapeDtypeStructs carrying their NamedShardings. Consumed by
        checkpoint-restore targets and by AOT lowering for worlds this
        process cannot execute on (speculative neighbor compilation: on a
        real multi-process mesh, running init from one process would hang
        on collectives its peers never joined — lowering does not)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        model, tx = self.spec.model, self.spec.optimizer
        features, _, _ = _split_batch(example_batch)
        root_key = jax.random.PRNGKey(self.seed)

        with jax.set_mesh(self.mesh):
            def _abstract(rng, feats):
                variables = model.init(
                    {"params": rng, "dropout": rng}, feats, training=False)
                return variables, tx.init(variables["params"])

            abstract, abstract_opt = jax.eval_shape(_abstract, root_key, features)
            param_shardings = nn.get_sharding(abstract, self.mesh)
            opt_shardings = nn.get_sharding(abstract_opt, self.mesh)
            repl = NamedSharding(self.mesh, P())

            def strip_boxes(tree):
                # nn.meta.unbox applies a sharding constraint (trace-only);
                # here we just want the boxed avals out of their metadata
                is_box = lambda x: isinstance(x, nn.meta.AxisMetadata)  # noqa: E731
                return jax.tree_util.tree_map(
                    lambda x: x.value if is_box(x) else x, tree, is_leaf=is_box
                )

            def sds(leaf, sharding):
                return jax.ShapeDtypeStruct(
                    tuple(leaf.shape), leaf.dtype, sharding=sharding)

            variables = jax.tree_util.tree_map(
                sds, strip_boxes(abstract), param_shardings)
            params = variables.pop("params")
            opt_state = jax.tree_util.tree_map(
                sds, strip_boxes(abstract_opt), opt_shardings)
            return TrainState(
                step=jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
                params=params,
                opt_state=opt_state,
                extra_vars=variables,
                rng=jax.ShapeDtypeStruct(
                    tuple(root_key.shape), root_key.dtype, sharding=repl),
            )

    # ------------------------------------------------------------------ #
    # Steps

    def _build_train_step(self):
        return jax.jit(self._raw_train_step(), donate_argnums=(0,))

    def _raw_train_step(self):
        model, tx, loss_fn = self.spec.model, self.spec.optimizer, self.spec.loss
        remat = self.remat
        remat_policy = self._resolved_remat_policy
        accum = self.grad_accum
        aux_weight = float(self.spec.aux_loss_weight or 0.0)
        aux_names = dict(self.spec.aux_loss_terms or {})

        def step_fn(state: TrainState, batch):
            features, labels, mask = _split_batch(batch)
            step_rng = jax.random.fold_in(state.rng, state.step)
            mutable = list(state.extra_vars.keys())

            def forward(variables, feats, rng):
                if mutable:
                    return model.apply(
                        variables, feats, training=True,
                        rngs={"dropout": rng}, mutable=mutable,
                    )
                return (
                    model.apply(variables, feats, training=True, rngs={"dropout": rng}),
                    {},
                )

            if remat:
                forward = jax.checkpoint(forward, policy=remat_policy)

            def compute_loss(params):
                variables = {"params": params, **state.extra_vars}
                outputs, new_vars = forward(variables, features, step_rng)
                value, terms = _loss_terms(loss_fn(labels, outputs))
                loss = _masked_mean(value, mask)
                terms = {k: _masked_mean(v, mask).astype(jnp.float32)
                         for k, v in terms.items()}
                terms.update(_aux_terms(new_vars, aux_weight, aux_names))
                return loss + _aux_loss(new_vars, aux_weight), (new_vars, terms)

            terms = {}      # accumulated micro-batches report the sum alone
            if accum > 1:
                loss_value, new_vars, grads = _accumulated_grads(
                    forward, loss_fn, state, features, labels, mask,
                    step_rng, accum, aux_weight=aux_weight,
                )
            else:
                (loss_value, (new_vars, terms)), grads = jax.value_and_grad(
                    compute_loss, has_aux=True
                )(state.params)
            with jax.named_scope("optimizer"):     # a name a trace can find
                updates, new_opt_state = tx.update(
                    grads, state.opt_state, state.params)
                new_params = optax.apply_updates(state.params, updates)
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt_state,
                extra_vars=new_vars,
            )
            return new_state, {**terms, "loss": loss_value.astype(jnp.float32)}

        return step_fn

    def _build_eval_step(self):
        return jax.jit(self._raw_eval_step())

    def _raw_eval_step(self):
        model, loss_fn = self.spec.model, self.spec.loss
        metric_items = tuple(self.metrics.items())

        def step_fn(state: TrainState, batch, metric_states):
            features, labels, mask = _split_batch(batch)
            variables = {"params": state.params, **state.extra_vars}
            outputs = model.apply(variables, features, training=False)
            new_states = dict(metric_states)
            for name, metric in metric_items:
                new_states[name] = metric.update(
                    metric_states[name], labels, outputs, mask
                )
            loss_value = _masked_scalar_loss(loss_fn, labels, outputs, mask)
            count = (
                jnp.sum(jnp.asarray(mask, jnp.float32))
                if mask is not None
                else jnp.float32(jnp.reshape(jnp.asarray(labels), (-1,)).shape[0])
            )
            new_states["_loss"] = metric_states["_loss"] + jnp.stack(
                [loss_value * count, count]
            )
            return new_states

        return step_fn

    def _build_predict_step(self):
        return jax.jit(self._raw_predict_step())

    def _raw_predict_step(self):
        model = self.spec.model

        def step_fn(state: TrainState, batch):
            features, _, _ = _split_batch(batch)
            variables = {"params": state.params, **state.extra_vars}
            return model.apply(variables, features, training=False)

        return step_fn

    # ------------------------------------------------------------------ #
    # Public API

    def train_step(self, state: TrainState, batch: Dict[str, Any]):
        fn = self._ensure("_train_step", "train_step", self._build_train_step)
        batch = mesh_lib.shard_batch(self.mesh, batch, self.spec.batch_partition)
        with jax.set_mesh(self.mesh):
            return self._dispatch("train_step", fn, state, batch)

    def train_many(self, state: TrainState, stacked_batch):
        """K train steps in ONE XLA dispatch: `lax.scan` of the step over a
        stacked batch pytree (leaves (K, B, ...) — build with
        `mesh.shard_batch_stack`). TPU-idiomatic dispatch amortization: the
        per-step host round-trip disappears (the reference pays a gRPC
        round-trip per minibatch — SURVEY §3.3). Returns (new_state,
        metrics stacked over the K steps)."""
        fn = self._ensure("_train_many", "train_many", self._build_train_many)
        with jax.set_mesh(self.mesh):
            return self._dispatch("train_many", fn, state, stacked_batch)

    def _build_train_many(self):
        """The scan-of-step program."""
        raw = self._raw_train_step()
        return jax.jit(
            lambda s, stacked: jax.lax.scan(raw, s, stacked),
            donate_argnums=(0,),
        )

    def train_step_cost(self, state: TrainState, batch) -> Dict[str, float]:
        """XLA cost analysis of ONE train step (the scan body `train_many`
        runs K times per dispatch): {'flops', 'bytes accessed'}. The SINGLE
        step is costed deliberately: XLA's cost analysis counts a
        `lax.scan` (while-loop) body ONCE regardless of trip count, so
        costing the train_many program would be ambiguous per-step.
        Matmul/conv FLOPs are exact (fusion never changes them); 'bytes
        accessed' counts intermediates and so upper-bounds real HBM
        traffic.

        The analysis comes from the COMPILED executable: the TPU backend is
        a PJRT C-API plugin, and on those jax's `Lowered.cost_analysis()`
        is not implemented (it returns None — asked on the v5e, PR 21), so
        the backend itself must be asked. That is an AOT compile of the
        single-step program, as long as its first compile unless the
        persistent cache already holds it — keep this off latency-sensitive
        paths. Memoized per (state, batch) aval signature, so a different
        batch shape gets its own analysis. A backend that cannot answer
        raises."""
        fn = self._ensure("_train_step", "train_step", self._build_train_step)
        batch = mesh_lib.shard_batch(self.mesh, batch, self.spec.batch_partition)
        key = _aval_signature((state, batch))
        if key not in self._cost_cache:
            with jax.set_mesh(self.mesh):
                ca = fn.lower(state, batch).compile().cost_analysis()
            d = ca if isinstance(ca, dict) else (ca[0] if ca else {})
            if not d.get("flops"):
                raise RuntimeError(
                    f"{jax.default_backend()} backend returned no FLOP count "
                    f"for the compiled train step: {ca!r}")
            self._cost_cache[key] = d
        d = self._cost_cache[key]
        return {
            "flops": float(d["flops"]),
            "bytes accessed": float(d.get("bytes accessed", 0.0)),
        }

    def set_learning_rate(self, state: TrainState, lr: float) -> TrainState:
        """Runtime LR change with no retrace — requires the zoo optimizer to
        be built via lr_modulation.modulated (injected hyperparams)."""
        from elasticdl_tpu.training import lr_modulation

        return state.replace(
            opt_state=lr_modulation.set_learning_rate(state.opt_state, lr)
        )

    def new_metric_states(self) -> Dict[str, np.ndarray]:
        states = metrics_lib.init_states(self.metrics)
        states["_loss"] = np.zeros((2,), np.float32)
        return states

    def eval_step(self, state: TrainState, batch, metric_states):
        fn = self._ensure("_eval_step", "eval_step", self._build_eval_step)
        batch = mesh_lib.shard_batch(self.mesh, batch, self.spec.batch_partition)
        with jax.set_mesh(self.mesh):
            return self._dispatch("eval_step", fn, state, batch, metric_states)

    def eval_many(self, state: TrainState, stacked_batch, metric_states):
        """K eval steps in ONE XLA dispatch: `lax.scan` of the eval step
        over a stacked batch pytree (build with `mesh.shard_batch_stack`) —
        the eval-stream twin of `train_many`'s dispatch amortization (the
        per-dispatch host round trip dominates small eval batches on a slow
        link). Streaming metric states are the scan carry, so the result is
        numerically equivalent to K sequential `eval_step` calls (the scan
        body compiles separately — XLA fusion may round the last bit
        differently)."""
        fn = self._ensure("_eval_many", "eval_many", self._build_eval_many)
        with jax.set_mesh(self.mesh):
            return fn(state, stacked_batch, metric_states)

    def _build_eval_many(self):
        raw = self._raw_eval_step()
        return jax.jit(
            lambda s, stacked, ms: jax.lax.scan(
                lambda carry, b: (raw(s, b, carry), None), ms, stacked
            )[0]
        )

    def predict_step(self, state: TrainState, batch):
        fn = self._ensure(
            "_predict_step", "predict_step", self._build_predict_step)
        batch = mesh_lib.shard_batch(self.mesh, batch, self.spec.batch_partition)
        with jax.set_mesh(self.mesh):
            return self._dispatch("predict_step", fn, state, batch)

    def predict_many(self, state: TrainState, stacked_batch):
        """K predict steps in ONE dispatch (`lax.map` over the stacked
        batch pytree): outputs come back stacked (K, B, ...) — the
        prediction twin of train_many/eval_many dispatch amortization."""
        fn = self._ensure(
            "_predict_many", "predict_many", self._build_predict_many)
        with jax.set_mesh(self.mesh):
            return fn(state, stacked_batch)

    def _build_predict_many(self):
        raw = self._raw_predict_step()
        return jax.jit(
            lambda s, stacked: jax.lax.map(lambda b: raw(s, b), stacked)
        )

    def metric_results(self, metric_states) -> Dict[str, float]:
        states = {k: np.asarray(jax.device_get(v)) for k, v in metric_states.items()}
        out = metrics_lib.results(self.metrics, {k: v for k, v in states.items() if k != "_loss"})
        loss_state = states.get("_loss")
        if loss_state is not None and loss_state[1] > 0:
            out["loss"] = float(loss_state[0] / loss_state[1])
        return out
