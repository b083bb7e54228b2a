"""Set-up under the program's own spans: `tracing.span(since=)`, the fold of
a process's start-up records (`tracing.startup_ledger`), the compile ledger of
observability/profile.py (JAX's own compile events on the innermost open
`compile` / `start.state` span, the rest counted as `outside`), and where
`Trainer` opens the spans: `init_state`, `_aot_compile`, the first jitted
dispatch of a (kind, aval signature)."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.observability import profile, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def records():
    """The tracer's in-memory records, emptied: what a test emits is all
    there is."""
    t = tracing.get_tracer()
    t.records.clear()
    return t.records


def _span(name, start, seconds, span_id, parent_id=None, **attrs):
    return {"kind": "span", "name": name, "trace_id": "t", "span_id": span_id,
            "parent_id": parent_id, "role": "worker-0", "ts": float(start),
            "dur_ms": 1e3 * seconds, **attrs}


# ---------------------------------------------------------------------- #
# the fold


SYNTHETIC = [
    _span("start.process", 100.0, 2.0, "a", role=""),
    _span("start.connect", 102.1, 0.4, "b"),
    {"kind": "event", "name": "membership.join", "ts": 102.3},
    _span("rescale", 102.0, 50.0, "z"),                 # no start-up span
    _span("start.state", 103.5, 1.5, "d", parent_id="c", programs=3,
          trace_s=0.2, lower_s=0.1, backend_s=1.0, cache_misses=2),
    _span("ckpt.restore", 105.0, 0.5, "e", parent_id="c"),
    _span("compile", 106.0, 3.0, "f", parent_id="c", program="train_many",
          aot=False, programs=1, trace_s=0.5, lower_s=0.25, backend_s=2.0,
          cache_load_s=0.125, cache_hits=1),
    _span("start.first_task", 103.0, 7.0, "c"),
    _span("compile", 120.0, 1.0, "g", program="eval_step", aot=False),
]


def test_the_fold_is_a_partition_of_the_wall():
    ledger = tracing.startup_ledger(SYNTHETIC, until=111.0)
    spans = ledger["spans"]
    assert set(spans) == {"start.process", "start.connect", "start.state",
                          "ckpt.restore", "compile", "start.first_task"}
    assert spans["start.first_task"]["s"] == 7.0
    assert spans["start.first_task"]["self_s"] == 7.0 - (1.5 + 0.5 + 3.0)
    assert spans["compile"]["n"] == 1 and spans["compile"]["self_s"] == 3.0
    # first start to last end, what lies under a span, and the gaps between
    assert (ledger["ts"], ledger["wall_s"]) == (100.0, 10.0)
    assert ledger["named_s"] == pytest.approx(2.0 + 0.4 + 7.0)
    assert ledger["cover"] == [[100.0, 102.0], [102.1, 102.5], [103.0, 110.0]]
    self_sum = sum(s["self_s"] for s in spans.values())
    gaps = ledger["wall_s"] - ledger["named_s"]
    assert self_sum + gaps == pytest.approx(ledger["wall_s"])
    # the compile ledger's attributes, summed by name and kept by program
    assert spans["start.state"]["cache_misses"] == 2
    assert spans["compile"]["each"] == [{
        "program": "train_many", "aot": False, "s": 3.0, "programs": 1,
        "trace_s": 0.5, "lower_s": 0.25, "backend_s": 2.0,
        "cache_load_s": 0.125, "cache_hits": 1}]
    # a span that closed before the process knew its role carries none
    assert ledger["role"] == "worker-0" and ledger["trace_id"] == "t"


def test_the_fold_s_cut_and_its_nothing():
    whole = tracing.startup_ledger(SYNTHETIC)
    assert whole["spans"]["compile"]["n"] == 2
    assert whole["wall_s"] == 21.0
    assert tracing.startup_ledger(SYNTHETIC, until=101.0) is None
    assert tracing.startup_ledger([SYNTHETIC[2], SYNTHETIC[3]]) is None
    assert tracing.startup_ledger(
        SYNTHETIC, outside={"programs": 4})["outside"] == {"programs": 4}


def test_a_child_that_outlasts_its_parent_by_a_tick_is_cut_to_it():
    ledger = tracing.startup_ledger([
        _span("start.state", 1.0, 1.0005, "k", parent_id="p"),
        _span("start.first_task", 1.0, 1.0, "p")])
    assert ledger["spans"]["start.first_task"]["self_s"] == 0.0


# ---------------------------------------------------------------------- #
# spans that began before they could be opened


def test_a_span_counts_from_since(records):
    since = time.time() - 5.0
    with tracing.span("start.launch", since=since) as launch:
        with tracing.span("start.master"):
            pass
    master, rec = list(records)
    assert rec["ts"] == since and 5000.0 <= rec["dur_ms"] < 5500.0
    assert master["parent_id"] == launch.span_id and master["ts"] > since


def test_start_spans_share_the_process_s_start_up_trace(records, monkeypatch):
    monkeypatch.setattr(tracing.get_tracer(), "startup_trace_id", None)
    tracing.join_startup_trace(None)            # nothing announced
    tracing.join_startup_trace("feedfacefeedface")
    with tracing.start_span("first_task"):
        with tracing.span("start.state"):
            pass
    with tracing.adopt("0123456789abcdef", "rpc"):  # an RPC's thread
        with tracing.start_span("spawn", worker_id=0):
            pass
    state, first_task, spawn = list(records)
    assert {r["trace_id"] for r in records} == {"feedfacefeedface"}
    assert state["parent_id"] == first_task["span_id"]
    assert (spawn["name"], spawn["parent_id"]) == ("start.spawn", None)


def test_the_process_s_start_is_the_kernel_s_or_the_entry_mark(monkeypatch):
    now = time.time()
    assert now - 3600 < tracing.process_start_ts() <= now
    import builtins

    def no_proc(path, *a, **kw):
        raise OSError(path)

    monkeypatch.setattr(tracing, "_entered_ts", None)
    tracing.mark_entry(now - 7.0)
    tracing.mark_entry(now)                     # the first mark stays
    monkeypatch.setattr(builtins, "open", no_proc)
    assert tracing.process_start_ts() == now - 7.0


# ---------------------------------------------------------------------- #
# the compile ledger


def _fresh_program():
    """A jitted function JAX has not seen: tracing, lowering and a backend
    compile, each reported once."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x: jnp.tanh(x) * 3 + 1)(np.ones((7,), np.float32))


def test_jax_s_figures_land_on_the_innermost_open_span(records):
    import jax  # noqa: F401  (the ledger needs it imported)

    assert profile.install_compile_ledger()
    assert profile.install_compile_ledger()             # and once only
    before = profile.compile_outside()
    with tracing.span("start.first_task"):              # takes no figures
        with tracing.span("start.state") as state:
            with tracing.span("compile", program="p", aot=False) as inner:
                _fresh_program()
            _fresh_program()
    assert profile.compile_outside() == before
    for handle in (inner, state):
        assert handle.attrs["programs"] == 1
        assert handle.attrs["backend_s"] > 0 and handle.attrs["trace_s"] > 0
    first_task = list(records)[-1]
    assert first_task["name"] == "start.first_task"
    assert "programs" not in first_task and "scratch" not in first_task
    # the three stages are intervals that do not overlap: their sum is wall
    compile_rec = next(r for r in records if r["name"] == "compile")
    staged = sum(compile_rec[k] for k in ("trace_s", "lower_s", "backend_s"))
    assert staged <= compile_rec["dur_ms"] / 1e3 + 1e-3


def test_a_compilation_under_no_program_span_is_counted_outside(records):
    import jax  # noqa: F401

    profile.install_compile_ledger()
    before = profile.compile_outside()
    with tracing.span("rescale.mesh"):                  # not a compile span
        _fresh_program()
    after = profile.compile_outside()
    assert after["programs"] == before.get("programs", 0) + 1
    assert after["backend_s"] > before.get("backend_s", 0)
    assert not any("programs" in r for r in records)


def test_a_nested_trace_is_taken_out_of_the_one_around_it():
    now = time.time()
    intervals = []
    assert profile._own_seconds(intervals, 1.0) == pytest.approx(1.0)
    assert profile._own_seconds(intervals, 0.5) == pytest.approx(0.5)
    # an event that ends now and began before both holds them
    assert profile._own_seconds(intervals, 10.0) == pytest.approx(8.5, abs=0.01)
    assert len(intervals) == 1 and intervals[0][1] >= now
    assert profile._own_seconds(intervals, 0.25) == pytest.approx(0.25)


# ---------------------------------------------------------------------- #
# where Trainer opens them


def _tiny_trainer():
    import jax

    from elasticdl_tpu.parallel.mesh import build_mesh
    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    cfg = JobConfig(
        model_zoo=os.path.join(REPO, "model_zoo"),
        model_def="deepfm.deepfm.custom_model",
        model_params={"field_vocab": 64, "hidden": "16,16"})
    spec = ModelSpec.from_config(cfg)
    mesh = build_mesh({"data": 1}, jax.devices()[:1])
    return Trainer(spec, mesh), spec, mesh


def _stack(mesh, spec, steps, batch=8):
    from elasticdl_tpu.parallel.mesh import shard_batch_stack

    rng = np.random.default_rng(steps)
    one = lambda: {  # noqa: E731
        "features": {"dense": rng.random((batch, 13), np.float32),
                     "cat": rng.integers(0, 1000, (batch, 26)).astype(np.int32)},
        "labels": rng.integers(0, 2, (batch,)).astype(np.int32),
        "mask": np.ones((batch,), np.float32)}
    batches = [one() for _ in range(steps)]
    return batches[0], shard_batch_stack(mesh, batches, spec.batch_partition)


def _named(records, name):
    return [r for r in records if r["name"] == name]


def test_a_real_trainer_s_start_up_folds_to_a_partition(records):
    trainer, spec, mesh = _tiny_trainer()
    example, stack = _stack(mesh, spec, 2)
    with tracing.start_span("first_task"):
        state = trainer.init_state(example)
        state, _ = trainer.train_many(state, stack)
    ledger = tracing.startup_ledger(records)
    spans = ledger["spans"]
    assert set(spans) == {"start.first_task", "start.state", "compile"}
    assert spans["start.state"]["programs"] >= 1
    assert spans["compile"]["each"][0]["program"] == "train_many"
    assert spans["compile"]["backend_s"] > 0
    assert sum(s["self_s"] for s in spans.values()) == pytest.approx(
        ledger["named_s"], abs=1e-3)
    assert ledger["named_s"] == pytest.approx(ledger["wall_s"], abs=1e-3)
    assert spans["start.first_task"]["self_s"] < spans["start.first_task"]["s"]


def test_the_first_dispatch_of_a_signature_compiles_under_a_span(records):
    """A check's stack of 2 steps, then a window's of 4: both first
    dispatches are `compile` spans, the pin settles on the third dispatch of
    one signature, and from then on a dispatch opens nothing and JAX reports
    nothing."""
    import jax.monitoring

    trainer, spec, mesh = _tiny_trainer()
    example, check = _stack(mesh, spec, 2)
    _, window = _stack(mesh, spec, 4)
    state = trainer.init_state(example)
    state, _ = trainer.train_many(state, check)
    state, _ = trainer.train_many(state, window)
    assert [(r["program"], r["aot"]) for r in _named(records, "compile")] == [
        ("train_many", False)] * 2
    assert all(r["programs"] == 1 for r in _named(records, "compile"))
    seen = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, *a, **kw: seen.append(event))
    for _ in range(3):
        state, _ = trainer.train_many(state, window)
    assert trainer._pinned_exe["train_many"][1] is None     # settled
    assert len(_named(records, "compile")) == 2 and not seen
    # a signature that comes back is not a first dispatch
    state, _ = trainer.train_many(state, check)
    assert len(_named(records, "compile")) == 2


def test_an_aot_compile_is_one_span_and_its_dispatch_none(records):
    trainer, spec, mesh = _tiny_trainer()
    example, stack = _stack(mesh, spec, 2)
    state = trainer.init_state(example)
    trainer.aot_compile_train_many(state, stack)
    trainer.aot_compile_train_many(state, stack)            # idempotent
    state, _ = trainer.train_many(state, stack)
    compiles = _named(records, "compile")
    assert [(r["program"], r["aot"]) for r in compiles] == [("train_many", True)]
    assert compiles[0]["lower_s"] > 0 and compiles[0]["backend_s"] > 0
    assert [r["model"] for r in _named(records, "start.state")] == [
        "deepfm.deepfm"]


# ---------------------------------------------------------------------- #
# the persistent cache: misses, then hits, in fresh processes


_TWO_DISPATCHES = """
import json, os, sys, types
import numpy as np
sys.path.insert(0, os.getcwd())
import jax
from elasticdl_tpu.common.runtime import configure_jax_runtime
from elasticdl_tpu.observability import profile, tracing
from tests.test_startup_ledger import _stack, _tiny_trainer

configure_jax_runtime(types.SimpleNamespace(
    compilation_cache_dir="", compilation_cache_min_compile_s=0.0))
trainer, spec, mesh = _tiny_trainer()
example, stack = _stack(mesh, spec, 2)
state = trainer.init_state(example)
state, _ = trainer.train_many(state, stack)
events = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, *a, **kw: events.append(event))
jax.monitoring.register_event_listener(lambda event, **kw: events.append(event))
spans = len(tracing.get_tracer().records)
state, m = trainer.train_many(state, stack)
jax.block_until_ready(m)
print(json.dumps({
    "ledger": tracing.startup_ledger(
        tracing.get_tracer().records, outside=profile.compile_outside()),
    "second_dispatch": {"events": events,
                        "spans": len(tracing.get_tracer().records) - spans}}))
"""


def _fresh_process(cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    proc = subprocess.run(
        [sys.executable, "-c", _TWO_DISPATCHES], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cold_counts_misses_and_warm_counts_hits(tmp_path):
    cold, warm = _fresh_process(tmp_path), _fresh_process(tmp_path)
    for run in (cold, warm):
        assert run["second_dispatch"] == {"events": [], "spans": 0}
        assert set(run["ledger"]["spans"]) == {"start.state", "compile"}

    def total(run, key):
        return sum(s.get(key, 0) for s in run["ledger"]["spans"].values())

    assert total(cold, "cache_misses") >= 2 and total(cold, "cache_hits") == 0
    assert cold["ledger"]["spans"]["compile"]["backend_s"] > 0
    assert total(cold, "cache_load_s") == 0
    assert total(warm, "cache_misses") == 0
    assert total(warm, "cache_hits") == total(cold, "cache_misses")
    assert total(warm, "cache_load_s") > 0
    # tracing and lowering are paid warm too
    assert warm["ledger"]["spans"]["compile"]["trace_s"] > 0
    assert warm["ledger"]["spans"]["compile"]["lower_s"] > 0
