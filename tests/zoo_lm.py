"""The one harness of the sparse-LM zoo's test files (not collected).

A test file states what is its model's — `ZooLM("mellum", tiny=..., reference=...,
driver=..., departures=..., seq=36, mutable=(...), sown={...}, lively=[...])` —
and gets `tiny_params`, `trainer`, `batches`, `lively`, `state` / `params`,
`program_terms`, `assignments`, `gradients` and `run_check` from here. What
differs between models is that data, never a branch on a model's name.

A trainer and the lively parameters of its initial state are built ONCE a
process for each (seed, configuration) and shared by the cases that run the
program as it is. A case that patches the program (`departures.applied`, a
`monkeypatch.setattr` on a zoo or `ops` module, an `EDL_FLASH` route) takes
`fresh_trainer` and traces `terms` inside its patch: a memoised trainer, and a
jit made outside the patch, hold the UNPATCHED compiled program.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import common
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.parallel.mesh import build_mesh
from elasticdl_tpu.training.model_spec import ModelSpec
from elasticdl_tpu.training.trainer import Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def preset(name):
    """A tiny preset of `benchmark/rehearse/`."""
    return common.load_json("rehearse", name)["model_params"]


# the rules `lively` scales a leaf by: (leaf, generator) -> leaf
def scaled(factor):
    return lambda leaf, r: leaf * factor


def jittered(leaf, r):
    return leaf * jnp.asarray(r.uniform(0.5, 1.5, leaf.shape), jnp.float32)


def drawn(scale):
    # placed as the leaf it replaces is: another sharding is another program to jit
    return lambda leaf, r: jax.device_put(
        jnp.asarray(r.normal(size=leaf.shape) * scale, jnp.float32), leaf.sharding)


def _once(method):
    """`method(self, seed=0, **more)` run once a harness for each seed and
    configuration, however the call spells them."""
    @functools.wraps(method)
    def memoised(self, seed=0, **more):
        key = (method.__name__, seed, tuple(sorted(more.items())))
        if key not in self._memo:
            self._memo[key] = method(self, seed, **more)
        return self._memo[key]

    return memoised


class ZooLM:
    def __init__(self, name, *, tiny, reference, seq, driver=None, departures=None,
                 lively=(), mutable=(), sown=None, training=False, short=None):
        self.model_def = f"transformer.{name}.custom_model"
        self.tiny, self.reference, self.seq = tiny, reference, seq
        self.driver, self.departures = driver, departures
        self.rules = lively             # [(leaf names, rule)], applied in this order
        self.mutable = list(mutable)    # the collections the model's apply may write
        self.sown = sown or {}          # {term: name under "losses"}, added to `loss`
        self.training = training        # how `terms` applies the model
        self.short = short or {}        # what the check's cases run of the preset
        self._memo = {}

    def tiny_params(self, **more):
        return {k: str(v) for k, v in {**self.tiny, **more}.items()}

    def fresh_trainer(self, seed=0, **more):
        """(spec, trainer) with a compiled step of its own."""
        cfg = JobConfig.from_argv([
            "--model_zoo", os.path.join(ROOT, "model_zoo"),
            "--model_def", self.model_def,
            "--model_params", common.format_model_params(self.tiny_params(**more))])
        spec = ModelSpec.from_config(cfg)
        return spec, Trainer(spec, build_mesh(devices=jax.devices()[:1]), seed=seed)

    @_once
    def trainer(self, seed=0, **more):
        """(spec, trainer), the same for the life of the process: for cases
        that run the program AS IT IS."""
        return self.fresh_trainer(seed, **more)

    @property
    def zoo(self):
        return sys.modules[self.trainer()[0].module_name]

    def batches(self, steps=2, batch=2, seq=None, seed=1):
        toks = np.random.default_rng(seed).integers(
            0, self.tiny["vocab_size"], (steps, batch, (seq or self.seq) + 1)).astype(np.int32)
        return [{"features": t[:, :-1], "labels": t[:, 1:],
                 "mask": np.ones((batch,), np.float32)} for t in toks]

    def lively(self, state, seed=5):
        """Parameters as a trained model has them rather than as the seed
        leaves them: the model's rules say which leaves and how."""
        r = np.random.default_rng(seed)
        p = dict(state.params)
        for names, rule in self.rules:
            for name in names:
                p[name] = rule(p[name], r)
        return state.replace(params=p)

    @_once
    def _lively_state(self, seed=0, **more):
        return self.lively(self.trainer(seed, **more)[1].init_state(self.batches(steps=1)[0]))

    def state(self, seed=0, **more):
        """The memoised trainer's lively initial state, as buffers of the
        caller's own: a step donates the state it is given."""
        return jax.tree_util.tree_map(jnp.copy, self._lively_state(seed, **more))

    def params(self, seed=0, **more):
        return self._lively_state(seed, **more).params

    def terms(self, spec, params, batch, variables=None):
        """The program's loss terms of a batch, traced where it is called."""
        outputs, written = spec.model.apply(
            {"params": params, **(variables or {})}, batch["features"],
            training=self.training, mutable=self.mutable)
        terms = spec.loss(batch["labels"], outputs)
        terms = ({k: jnp.mean(v) for k, v in terms.items()} if isinstance(terms, dict)
                 else {"loss": jnp.mean(terms)})
        for term, name in self.sown.items():
            terms[term] = written["losses"][name]
            terms["loss"] = terms["loss"] + terms[term]
        return terms

    @_once
    def program_terms(self, seed=0, **more):
        """`terms` of the program as it is, jitted once a configuration:
        (params, batch[, variables]) -> terms."""
        spec, _ = self.trainer(seed, **more)
        return jax.jit(functools.partial(self.terms, spec))

    @_once
    def assignments(self, seed=0, **more):
        """The zoo's `expert_assignments` of the program as it is, jitted
        once a configuration: the arguments before its `cfg`."""
        spec, _ = self.trainer(seed, **more)
        return jax.jit(lambda *args: self.zoo.expert_assignments(*args, spec.model.cfg))

    def gradients(self, reference_loss, variables=None):
        """((total, terms), gradients) of one batch from the same lively
        parameters, the program's and the reference's; `reference_loss(p,
        batch, hyper) -> (total, terms)`."""
        spec, _ = self.trainer()
        batch, params = self.batches(steps=1)[0], self.params()
        hp = self.reference.hyper(self.tiny_params())
        ref_batch = {"tokens": batch["features"], "labels": batch["labels"],
                     "mask": batch["mask"]}

        def program_loss(p):
            terms = self.terms(spec, p, batch, variables)
            return terms["loss"], terms

        with jax.default_matmul_precision("highest"):
            got = jax.jit(jax.value_and_grad(program_loss, has_aux=True))(params)
            want = jax.jit(jax.value_and_grad(
                lambda p: reference_loss(p, ref_batch, hp), has_aux=True))(params)
        return got, want

    def run_check(self, departure=None):
        """The benchmark's check, as the model's driver drives it, under the
        reference's `TOLERANCES` and `EXPERT_PAIRS_FLOOR` as the test has set
        them: the program as it is on the memoised trainer, a departure on a
        trainer of its own. Both start from the memoised initial state, which
        is arrays and holds nothing of the program."""
        spec, trainer = (self.fresh_trainer if departure else self.trainer)(**self.short)
        with self.departures.applied(departure, self.zoo):
            return self.driver.program_check(
                trainer, spec, trainer.mesh, self.zoo, self.reference,
                self.tiny_params(**self.short), self.batches(),
                lambda: self.state(**self.short), lambda text: None)
