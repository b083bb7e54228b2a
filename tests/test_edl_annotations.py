"""The worker loop's own spans in the device profiler's trace
(observability/profile.py::annotation): a tiny job through each of the three
task loops under `jax.profiler.trace` leaves an `.xplane.pb` whose host plane
holds the `edl.*` span set on the task loop's thread, nested as
docs/observability.md lists it; the seam stays jax-free at import; the task
line's ms/step is the `edl.compute` spans' time."""

import glob
import logging
import os
import re
import subprocess
import sys
import threading
import time

import pytest

from elasticdl_tpu.client.local import free_port
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.master.main import Master
from elasticdl_tpu.observability import profile, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------- #
# reading a trace

class Span:
    def __init__(self, name, start, end, stats):
        self.name, self.start, self.end, self.stats = name, start, end, stats
        self.parent, self.children = None, []

    def path(self):
        return (self.parent.path() if self.parent else ()) + (self.name,)

    def under(self, name):
        return [c for c in self.children if c.name == name]

    def __repr__(self):
        return f"{'/'.join(self.path())}{self.stats}"


def read_lines(trace_dir):
    """{line index: [Span]} of the `edl.*` events on the host plane, each
    line's spans nested by containment."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    assert paths, f"no .xplane.pb under {trace_dir}"
    lines = {}
    profile_data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    index = 0
    for plane in profile_data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                          dict(ev.stats))
                     for ev in line.events if ev.name.startswith("edl.")]
            if not spans:
                continue
            spans.sort(key=lambda s: (s.start, -s.end))
            stack = []
            for s in spans:
                while stack and stack[-1].end <= s.start:
                    stack.pop()
                if stack:
                    s.parent = stack[-1]
                    stack[-1].children.append(s)
                stack.append(s)
            lines[index] = spans
            index += 1
    return lines


def task_loop(lines):
    loops = [spans for spans in lines.values()
             if any(s.name == "edl.task_turn" for s in spans)]
    assert len(loops) == 1, "edl.task_turn on one thread and one only"
    return loops[0]


# ---------------------------------------------------------------------- #
# a tiny job through a task loop, traced

def job_config(**overrides):
    base = dict(
        job_name="edl-spans",
        model_zoo=os.path.join(REPO, "model_zoo"),
        model_def="deepfm.deepfm.custom_model",
        model_params={"field_vocab": 64, "hidden": "16,16"},
        training_data="synthetic://criteo?n=1024&shards=2",
        records_per_task=512,
        minibatch_size=64,
        num_epochs=1,
        evaluation_steps=0,
        num_workers=1,
        master_addr=f"localhost:{free_port()}",
        worker_heartbeat_s=1.0,
        task_timeout_s=300.0,
        shuffle=False,
        metrics_port=-1,
    )
    base.update(overrides)
    return JobConfig(**base)


class _Lines(logging.Handler):
    def __init__(self):
        super().__init__()
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


def run_traced(make_loop, cfg, trace_dir):
    """Master in process, the loop's `run()` on a thread of its own, the
    whole of it under one profiler session. Returns (lines of the trace,
    the loop's log lines)."""
    import jax

    profile.reset_for_tests()
    master = Master(cfg)
    master.start()
    loop = make_loop(cfg)
    seen = _Lines()
    logging.getLogger("elasticdl_tpu").addHandler(seen)
    rc = {}
    t = threading.Thread(target=lambda: rc.update(v=loop.run()), daemon=True)
    try:
        with jax.profiler.trace(str(trace_dir)):
            t.start()
            deadline = time.time() + 240
            while t.is_alive() and time.time() < deadline:
                master.membership.reap()
                master.dispatcher.poke()
                time.sleep(0.05)
            t.join(timeout=5)
        assert not t.is_alive(), "the loop did not finish the job"
        assert rc["v"] == 0, rc
        assert master.dispatcher.counts()["finished_training"] == 2
    finally:
        logging.getLogger("elasticdl_tpu").removeHandler(seen)
        master.server.stop(grace=0)
        tracing.get_tracer().configure(path=None)
        profile.reset_for_tests()
    return read_lines(trace_dir), seen.lines


def _worker(cfg):
    from elasticdl_tpu.worker.worker import Worker

    return Worker(cfg)


def _cohort(cfg):
    from elasticdl_tpu.parallel.elastic import CohortContext
    from elasticdl_tpu.worker.cohort import CohortWorker

    class OneProcess(CohortContext):
        """A world of one needs no coordinator."""

        def initialize(self):
            pass

    return CohortWorker(cfg, ctx=OneProcess("localhost:1", 1, 0))


LOOPS = {
    # 512-record tasks at batch 64: two dispatches of four steps a task
    "worker-grouped": (_worker, dict(steps_per_dispatch=4)),
    # one step a dispatch: the batches come through the DevicePrefetcher
    "worker-prefetched": (_worker, dict(steps_per_dispatch=1)),
    "cohort-grouped": (_cohort, dict(steps_per_dispatch=4, num_processes=1)),
}


@pytest.fixture(scope="module", params=sorted(LOOPS))
def traced(request, tmp_path_factory):
    make_loop, overrides = LOOPS[request.param]
    lines, log = run_traced(
        make_loop, job_config(**overrides),
        tmp_path_factory.mktemp(request.param))
    return request.param, lines, log


def training_turns(lines):
    return [s for s in task_loop(lines)
            if s.name == "edl.task_turn" and s.stats.get("type") == "TRAINING"]


def test_the_task_turn_and_what_it_holds(traced):
    kind, lines, _ = traced
    turns = training_turns(lines)
    assert len(turns) == len({t.stats["task_id"] for t in turns}) == 2
    # the worker's first turn is the last part of its start-up (the cohort's
    # loop has Trainer's start-up spans alone)
    first = None if kind == "cohort-grouped" else "edl.start.first_task"
    assert [t.parent and t.parent.name for t in turns] == [first, None]
    for turn in turns:
        assert len(turn.under("edl.lease")) == 1
        assert len(turn.under("edl.task")) == 1
        assert len(turn.under("edl.report")) == 1
        lease, task, report = (turn.under(n)[0] for n in (
            "edl.lease", "edl.task", "edl.report"))
        assert lease.end <= task.start and task.end <= report.start
        assert task.stats["records"] == 512
    # the turn that ends the job leases and holds no task
    last = [s for s in task_loop(lines) if s.name == "edl.task_turn"][-1]
    assert not last.under("edl.task")


def test_inside_the_task(traced):
    kind, lines, _ = traced
    steps, per_task = (1, 8) if kind == "worker-prefetched" else (4, 2)
    for turn in training_turns(lines):
        task = turn.under("edl.task")[0]
        computes = task.under("edl.compute")
        assert len(computes) == per_task
        assert [c.stats["steps"] for c in computes] == [steps] * per_task
        # every host batch was pulled under edl.data_wait, inside the task,
        # the first of the task included (and the pull that finds the end)
        assert len(task.under("edl.data_wait")) >= 8
        assert task.under("edl.data_wait")[0].start < computes[0].start
        for c in computes:
            assert [s.name for s in c.children if s.name != "edl.h2d"] == [
                "edl.compute.dispatch", "edl.compute.readback"]
        h2d_in_compute = sum(len(c.under("edl.h2d")) for c in computes)
        h2d_in_task = len(task.under("edl.h2d"))
        if kind == "worker-grouped":    # the stack is inside the timed region
            assert (h2d_in_compute, h2d_in_task) == (per_task, 0)
        elif kind == "cohort-grouped":  # and before it in the cohort's loop
            assert (h2d_in_compute, h2d_in_task) == (0, per_task)
        else:                           # the prefetcher's _put, a batch each
            assert (h2d_in_compute, h2d_in_task) == (0, 8)


def test_every_annotation_is_the_programs_and_none_the_benchmarks(traced):
    _, lines, _ = traced
    names = {s.name for spans in lines.values() for s in spans}
    assert names >= {"edl.task_turn", "edl.lease", "edl.task", "edl.data_wait",
                     "edl.h2d", "edl.compute", "edl.compute.dispatch",
                     "edl.compute.readback", "edl.report",
                     "edl.input.make_batch"}
    assert not [n for n in names if n.startswith("bench.")]


def test_the_parse_pool_is_on_threads_of_its_own(traced):
    _, lines, _ = traced
    loop = task_loop(lines)
    pool = [s for spans in lines.values() if spans is not loop
            for s in spans if s.name == "edl.input.make_batch"]
    assert len(pool) == 16 and all(s.stats["records"] == 64 for s in pool)
    assert not [s for s in loop if s.name == "edl.input.make_batch"]


def test_ms_per_step_is_the_compute_spans(traced):
    kind, lines, log = traced
    said = {}
    for line in log:
        m = re.search(r"training task (\d+): (\d+) step\(s\), ([\d.]+) ms/step", line)
        if m:
            said[int(m.group(1))] = (int(m.group(2)), float(m.group(3)))
    if kind == "cohort-grouped":
        assert not said         # the cohort's loop prints no task line
        return
    turns = training_turns(lines)
    assert sorted(said) == sorted(t.stats["task_id"] for t in turns)
    for turn in turns:
        steps, ms = said[turn.stats["task_id"]]
        computes = turn.under("edl.task")[0].under("edl.compute")
        assert sum(c.stats["steps"] for c in computes) == steps == 8
        spans_ms = sum(c.end - c.start for c in computes) / 1e6 / steps
        # the region's timer closes around the annotation: the log rounds
        # to 0.1 ms, the two clocks differ by microseconds
        assert abs(spans_ms - ms) <= 0.05 + 0.02, (spans_ms, ms)


# ---------------------------------------------------------------------- #
# the seam itself

def test_the_seam_imports_no_jax_and_works_without_it():
    code = (
        "import sys\n"
        "import elasticdl_tpu.observability.profile as p\n"
        "import elasticdl_tpu.observability.tracing as t\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "prof = p.StepProfiler()\n"
        "with prof.phase('compute', steps=2) as region:\n"
        "    with prof.phase('h2d') as put:\n"
        "        pass\n"
        "    region.carve(put.seconds)\n"
        "with prof.span('task_turn') as turn:\n"
        "    turn.set_metadata(task_id=1, type='TRAINING')\n"
        "with t.span('ckpt.save'):\n"
        "    pass\n"
        "assert list(p.timed_iter([1, 2], prof)) == [1, 2]\n"
        "prof.step_done(2)\n"
        "assert prof.snapshot(update_memory=False)['profiled_steps'] == 2\n"
        "assert region.seconds >= put.seconds > 0\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, text=True,
                         capture_output=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr[-2000:]


def test_one_module_opens_trace_annotations():
    """The program has one door into the profiler's trace."""
    hits = []
    for root, _, files in os.walk(os.path.join(REPO, "elasticdl_tpu")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path, errors="replace") as f:
                    if "TraceAnnotation" in f.read():
                        hits.append(os.path.relpath(path, REPO))
    assert hits == ["elasticdl_tpu/observability/profile.py"]


def test_a_nested_phase_is_carved_out_of_the_one_around_it():
    """The grouped worker path's repair: the stack is billed to h2d and the
    rest of the timed region to compute; the two sum to the region."""
    prof = profile.StepProfiler(window=4)
    with prof.phase("compute", steps=4) as region:
        with prof.phase("h2d") as put:
            time.sleep(0.02)
        region.carve(put.seconds)
        time.sleep(0.01)
    prof.step_done(4)
    snap = prof.snapshot(update_memory=False)
    assert snap["phase_h2d_ms"] == pytest.approx(1e3 * put.seconds / 4, abs=1e-3)
    assert snap["phase_h2d_ms"] >= 5.0 and snap["phase_compute_ms"] >= 2.5
    assert (snap["phase_h2d_ms"] + snap["phase_compute_ms"]
            == pytest.approx(1e3 * region.seconds / 4, abs=2e-3))


def _spans_of(trace_dir):
    return [s for spans in read_lines(trace_dir).values() for s in spans]


def test_a_phase_and_a_span_are_in_the_trace_with_their_attributes(tmp_path):
    import jax

    prof = profile.StepProfiler()
    with jax.profiler.trace(str(tmp_path)):
        with prof.span("task_turn") as turn:
            turn.set_metadata(task_id=7, type="TRAINING")
            with prof.phase("compute", steps=3):
                for _ in profile.timed_iter([1], prof):
                    pass
    by_name = {s.name: s for s in _spans_of(tmp_path)}
    assert by_name["edl.task_turn"].stats == {"task_id": 7, "type": "TRAINING"}
    assert by_name["edl.compute"].stats == {"steps": 3}
    assert by_name["edl.data_wait"].path()[:2] == ("edl.task_turn", "edl.compute")


def test_a_tracing_span_is_in_the_device_trace_too(tmp_path):
    """ckpt.save, rescale.mesh / .compile / .handoff: no cell shows
    them yet, the bridge is one call in tracing.span."""
    import jax

    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("rescale", reason="test"):
            with tracing.span("rescale.mesh"):
                pass
    by_name = {s.name: s for s in _spans_of(tmp_path)}
    assert by_name["edl.rescale.mesh"].parent is by_name["edl.rescale"]


def _tiny_trainer(model_def):
    """(trainer, state, batch): a Criteo zoo model at toy size on one device."""
    import jax
    import numpy as np

    from elasticdl_tpu.parallel.mesh import build_mesh
    from elasticdl_tpu.training.model_spec import ModelSpec
    from elasticdl_tpu.training.trainer import Trainer

    cfg = job_config(model_def=model_def)
    trainer = Trainer(ModelSpec.from_config(cfg),
                      build_mesh({"data": 1}, jax.devices()[:1]))
    rng = np.random.default_rng(0)
    batch = {"features": {"dense": rng.random((8, 13), np.float32),
                          "cat": rng.integers(0, 1000, (8, 26)).astype(np.int32)},
             "labels": rng.integers(0, 2, (8,)).astype(np.int32),
             "mask": np.ones((8,), np.float32)}
    return trainer, trainer.init_state(batch), batch


def test_an_aot_compile_says_so_in_the_trace(tmp_path):
    import jax

    trainer, state, batch = _tiny_trainer("deepfm.deepfm.custom_model")
    with jax.profiler.trace(str(tmp_path)):
        trainer.aot_compile_train_step(state, batch)
    compiles = [s for s in _spans_of(tmp_path) if s.name == "edl.compile"]
    assert [s.stats for s in compiles] == [{"program": "train_step", "aot": 1}]


# ---------------------------------------------------------------------- #
# the device half: names in the compiled program, nothing else

@pytest.mark.parametrize("model_def, scopes", [
    ("deepfm.deepfm.custom_model",
     ("emb/fwd/gather", "criteo/fm", "criteo/tower", "criteo/loss")),
    ("deepfm.xdeepfm.custom_model",
     ("emb/fwd/gather", "criteo/fm", "criteo/cin", "criteo/tower", "criteo/loss")),
])
def test_the_criteo_models_forward_scopes_are_in_the_programs_metadata(model_def, scopes):
    trainer, state, batch = _tiny_trainer(model_def)
    text = trainer.aot_compile_train_step(state, batch).as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in scopes:
        assert any(scope in n for n in names), (scope, sorted(names)[:20])
