"""Always-on step profiler: per-step phase attribution + memory watermarks.

The health layer (PR 6) can say a worker is slow; nothing says WHY. This
module attributes each train step's wall time to phases —

    data_wait   blocking on the input pipeline (reader/parse/shard fill;
                the prefetcher times its source pulls here)
    h2d         host->device transfer dispatch (prefetcher `device_put` /
                cohort global-batch assembly)
    compute     the step dispatch + device compute (the worker's timed
                region, which ends in the scalar readback)
    handoff     rescale/reform work landing on the step path (live state
                handoff, drained-batch requeues)

— and tracks host/device memory watermarks. Always on: the cost per step
is a few perf_counter reads and float adds under a leaf lock (bench.py's
`obs_overhead` leg gates it at <= 2% median step time).

It is also the program's one door into the device profiler's trace:
`annotation(name)` opens a `jax.profiler.TraceAnnotation` named `edl.<name>`
— a host span on the clock the device's events are on — and is the only
place in the program that does. `phase()` and `timed_iter` open one per
region (`edl.data_wait`, `edl.h2d`, `edl.compute`, `edl.handoff`);
`StepProfiler.span()` opens one that feeds no rolling window (the task turn:
`edl.task_turn`, `edl.lease`, `edl.task`, `edl.report`). An annotation is
always opened: with no profiler session running it costs a `TraceMe` (under a
microsecond), and in a process that never imported jax it is a no-op.

And it keeps the compile ledger: one pair of `jax.monitoring` listeners
(`install_compile_ledger`, once a process) takes JAX's own figures of every
compilation — tracing, lowering, the backend's compile or the persistent
cache's load, with its hits and misses — and adds them to the `compile` /
`start.state` span (observability/tracing.py) open around it on the compiling
thread. What compiles under no such span is counted apart
(`compile_outside`). JAX calls the listeners when it compiles and at no other
time.

Exports:

- gauges `edl_step_phase_seconds{phase=...}` (rolling per-step mean over
  the window) and `edl_mem_host_rss_mb` / `edl_mem_device_peak_mb`
  (watermarks, refreshed at snapshot time — never per step);
- `snapshot()`: the compact dict that rides the existing heartbeat stats
  payload (observability/health.py), so the master's ClusterHealth sees
  *why* a straggler is slow, not just that it is;
- flight-bundle integration: FlightRecorder.bundle() embeds the snapshot.

Stdlib-only at import; the device-memory probe and the annotations read jax
from `sys.modules` (guarded — absence degrades to host-only watermarks and
no annotations).
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, Iterable, Iterator, Optional

from elasticdl_tpu.observability.registry import default_registry

#: the phase vocabulary (snapshot keys are phase_<name>_ms)
PHASES = ("data_wait", "h2d", "compute", "handoff")

#: rolling window (steps) the per-phase means are computed over
WINDOW_DEFAULT = 128

_reg = default_registry()
_PHASE_S = _reg.gauge(
    "edl_step_phase_seconds",
    "rolling per-step mean wall time attributed to each step phase",
    labels=("phase",))
_MEM_HOST = _reg.gauge(
    "edl_mem_host_rss_mb", "host RSS high-water mark (MB)")
_MEM_DEV = _reg.gauge(
    "edl_mem_device_peak_mb",
    "device memory high-water mark (MB; 0 when the backend exposes none)")


#: profiler phase -> goodput-ledger category (observability/goodput.py).
#: `handoff` is deliberately ABSENT: rescale seconds are attributed at
#: the rescale sites themselves (with settle/handoff/compile sub-buckets)
#: and teeing the profiler's handoff too would double-bill them.
PHASE_TO_GOODPUT = {
    "data_wait": "data_wait",
    "h2d": "h2d",
    "compute": "train_compute",
}


#: every annotation's name starts with this (never `bench.`: the benchmark's
#: trace reduction takes its window from annotations under that prefix)
ANNOTATION_PREFIX = "edl."


class _NoAnnotation:
    """What `annotation` returns in a process without jax."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **attrs) -> None:
        pass


_NO_ANNOTATION = _NoAnnotation()


def annotation(name: str, **attrs):
    """A context manager that is the span `edl.<name>` in the device
    profiler's trace while a session runs (`jax.profiler.start_trace`, the
    worker's `--profile_dir` window), with `attrs` as the event's stats;
    `set_metadata(**attrs)` on it adds those known only inside the span."""
    jax = sys.modules.get("jax")    # never IMPORT jax from here
    if jax is None:
        return _NO_ANNOTATION
    return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name, **attrs)


# ---------------------------------------------------------------------- #
# the compile ledger

#: the spans that take JAX's compile figures: the innermost open one wins
COMPILE_SPANS = ("compile", "start.state")
#: JAX's duration events -> the attribute each adds to. The first three are
#: intervals that nest (a jitted function traced inside another's trace, a
#: trace inside a lowering): an inner one is taken out of the one around it,
#: so the three add up to wall time. `backend_s` is the backend call whole —
#: the compile, or on a hit the cache's load, which `cache_load_s` repeats.
_NESTING_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_DURATION_EVENTS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
    "/jax/compilation_cache/compile_time_saved_sec": "cache_saved_s",
}
#: a miss is counted where JAX writes the entry: a program that compiles
#: faster than `jax_persistent_cache_min_compile_time_secs` is neither
_COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"

_ledger_lock = threading.Lock()
_ledger_installed = False                       # guarded_by: _ledger_lock
_outside: Dict[str, float] = {}                 # guarded_by: _ledger_lock
_outside_local = threading.local()              # a thread's open intervals


def _own_seconds(intervals: list, seconds: float) -> float:
    """`seconds` ending now, less what `intervals` (the maximal ones this
    thread has credited so far) already hold of it; `intervals` is updated.
    Events come in the order they end, nested or apart, never crossing."""
    end = time.time()
    start = end - seconds
    inside = 0.0
    while intervals and intervals[-1][0] >= start - 1e-4:
        s, e = intervals.pop()
        inside += e - s
    intervals.append((start, end))
    return max(0.0, seconds - inside)


def _credit(key: str, amount: float, nests: bool = False) -> None:
    from elasticdl_tpu.observability import tracing     # tracing imports us

    span = tracing.open_span(COMPILE_SPANS)
    if span is not None:
        if nests:
            if span.scratch is None:
                span.scratch = []       # the intervals credited so far
            amount = _own_seconds(span.scratch, amount)
        span.attrs[key] = span.attrs.get(key, 0) + amount
        return
    if nests:
        if not hasattr(_outside_local, "intervals"):
            _outside_local.intervals = []
        amount = _own_seconds(_outside_local.intervals, amount)
    with _ledger_lock:
        _outside[key] = _outside.get(key, 0) + amount


def _on_duration(event: str, duration: float, **_) -> None:
    key = _NESTING_EVENTS.get(event)
    if key is not None:
        _credit(key, float(duration), nests=True)
        if event == _BACKEND_EVENT:
            _credit("programs", 1)
        return
    key = _DURATION_EVENTS.get(event)
    if key is not None:
        _credit(key, float(duration))


def _on_event(event: str, **_) -> None:
    key = _COUNT_EVENTS.get(event)
    if key is not None:
        _credit(key, 1)


def install_compile_ledger() -> bool:
    """Register the ledger's two listeners with `jax.monitoring`. Once a
    process, whoever calls (`Trainer.__init__` does); False in a process
    that has not imported jax."""
    global _ledger_installed
    jax = sys.modules.get("jax")    # never IMPORT jax from here
    if jax is None:
        return False
    with _ledger_lock:
        if not _ledger_installed:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _ledger_installed = True
    return True


def compile_outside() -> Dict[str, float]:
    """What JAX compiled in this process under no `compile` / `start.state`
    span, by the ledger's attribute names: helper programs (a gather, a
    transfer's reshape), a benchmark's reference program, a second shape
    dispatched after its kind's pin had settled. It enters no metric."""
    with _ledger_lock:
        return {k: round(v, 4) if isinstance(v, float) else v
                for k, v in _outside.items()}


class Region:
    """What `StepProfiler.phase` yields. `seconds` is the region's wall once
    it has closed — the one reading the caller's own figures use too (the
    task line's `ms/step`), so that no second timer runs. `carve(s)` takes
    out of this phase's bill what a phase nested in it has billed already
    (`h2d` inside `compute`): the two then sum to the region's wall."""

    __slots__ = ("seconds", "_carved")

    def __init__(self):
        self.seconds = 0.0
        self._carved = 0.0

    def carve(self, seconds: float) -> None:
        self._carved += seconds


class StepProfiler:
    """Accumulate phase seconds into the CURRENT step, roll them into the
    window at `step_done()`. Thread-safe (heartbeat threads snapshot while
    the train loop observes); the lock is a LEAF lock.

    `ledger` (a goodput.GoodputLedger) receives a tee of every phase add
    through PHASE_TO_GOODPUT — the goodput ledger's train/data/h2d
    attribution costs no second timer on the hot path. The process
    singleton (`get_profiler`) wires the process ledger; direct
    constructions opt in explicitly (bench.py's obs_overhead ON leg
    does, so the tee's cost stays inside the measured <=2% gate)."""

    def __init__(self, window: int = WINDOW_DEFAULT, ledger=None):
        self._ledger = ledger
        self._lock = threading.Lock()
        self._acc: Dict[str, float] = {}                 # guarded_by: _lock
        # per-phase rolling windows with maintained sums (mean is O(1))
        self._win: Dict[str, "deque[float]"] = {         # guarded_by: _lock
            p: deque(maxlen=window) for p in PHASES
        }
        self._sums: Dict[str, float] = {p: 0.0 for p in PHASES}  # guarded_by: _lock
        self._steps = 0                                  # guarded_by: _lock
        self._host_peak_mb = 0.0                         # guarded_by: _lock
        self._dev_peak_mb = 0.0                          # guarded_by: _lock

    # ------------------------------------------------------------------ #
    # hot path

    def add(self, phase: str, seconds: float) -> None:
        """Accumulate `seconds` into the current step's `phase` bucket
        (phases outside PHASES are accepted but dropped at step_done —
        bounded keys keep the heartbeat payload inside its size budget)."""
        if seconds <= 0:
            return
        with self._lock:
            self._acc[phase] = self._acc.get(phase, 0.0) + seconds
        if self._ledger is not None:
            category = PHASE_TO_GOODPUT.get(phase)
            if category is not None:
                self._ledger.add(category, seconds)

    @contextmanager
    def phase(self, name: str, **attrs) -> Iterator[Region]:
        """Time the body into `name`'s bucket and show it as `edl.<name>`
        in a device trace. The annotation lies inside the timed region."""
        region = Region()
        t0 = time.perf_counter()
        try:
            with annotation(name, **attrs):
                yield region
        finally:
            region.seconds = time.perf_counter() - t0
            self.add(name, region.seconds - region._carved)

    @staticmethod
    def span(name: str, **attrs):
        """`edl.<name>` around something that is no step phase (the task
        turn, a dispatch inside `compute`): an annotation and nothing
        else — no bucket, no window, no goodput."""
        return annotation(name, **attrs)

    def step_done(self, steps: int = 1) -> None:
        """Close the current step (or group of `steps` steps — grouped
        dispatch normalizes to per-step values so grouped and single-step
        workers report comparably) into the rolling windows."""
        n = max(1, int(steps))
        with self._lock:
            acc, self._acc = self._acc, {}
            self._steps += n
            for phase in PHASES:
                v = acc.pop(phase, 0.0) / n
                win = self._win[phase]
                if len(win) == win.maxlen:
                    self._sums[phase] -= win[0]
                win.append(v)
                self._sums[phase] += v
            # leftovers under non-standard keys are dropped (see add())
        for phase in PHASES:
            _PHASE_S.set(self._mean(phase), phase=phase)

    def _mean(self, phase: str) -> float:
        with self._lock:
            win = self._win[phase]
            return self._sums[phase] / len(win) if win else 0.0

    # ------------------------------------------------------------------ #
    # watermarks (snapshot cadence, never per step)

    def update_memory(self) -> None:
        """Refresh host/device memory watermarks. Best-effort: the host
        side is stdlib `resource` (ru_maxrss), the device side asks jax's
        per-device `memory_stats()` when the backend exposes it."""
        host_mb = 0.0
        try:
            import resource

            ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            # linux reports KB, macOS bytes; normalize to MB
            host_mb = ru / 1024.0 if os.uname().sysname != "Darwin" \
                else ru / (1024.0 * 1024.0)
        except Exception:
            # no resource module / exotic platform — host watermark stays 0:
            # edl-lint: disable=EDL303
            pass
        dev_mb = 0.0
        try:
            import sys

            jax = sys.modules.get("jax")   # never IMPORT jax from here —
            if jax is not None:            # only read it if the process did
                for d in jax.local_devices():
                    stats = getattr(d, "memory_stats", lambda: None)()
                    if stats:
                        dev_mb += float(
                            stats.get("peak_bytes_in_use",
                                      stats.get("bytes_in_use", 0))
                        ) / (1024.0 * 1024.0)
        except Exception:
            # a backend without memory_stats degrades to host-only:
            # edl-lint: disable=EDL303
            dev_mb = 0.0
        with self._lock:
            self._host_peak_mb = max(self._host_peak_mb, host_mb)
            self._dev_peak_mb = max(self._dev_peak_mb, dev_mb)
            host_peak, dev_peak = self._host_peak_mb, self._dev_peak_mb
        _MEM_HOST.set(host_peak)
        _MEM_DEV.set(dev_peak)

    # ------------------------------------------------------------------ #

    def snapshot(self, update_memory: bool = True) -> Dict[str, Any]:
        """The compact per-process profile row the heartbeat payload (and
        the flight bundle) carries: per-step phase means (ms) for phases
        with data, plus the memory watermarks."""
        if update_memory:
            self.update_memory()
        out: Dict[str, Any] = {}
        with self._lock:
            steps = self._steps
            for phase in PHASES:
                win = self._win[phase]
                if win and self._sums[phase] > 0:
                    out[f"phase_{phase}_ms"] = round(
                        1e3 * self._sums[phase] / len(win), 3
                    )
            host_peak, dev_peak = self._host_peak_mb, self._dev_peak_mb
        if steps:
            out["profiled_steps"] = steps
        if host_peak:
            out["mem_host_mb"] = round(host_peak, 1)
        if dev_peak:
            out["mem_dev_mb"] = round(dev_peak, 1)
        return out

    def reset(self) -> None:
        with self._lock:
            self._acc = {}
            for p in PHASES:
                self._win[p].clear()
                self._sums[p] = 0.0
            self._steps = 0
            self._host_peak_mb = self._dev_peak_mb = 0.0


def timed_iter(iterable: Iterable, profiler: "StepProfiler",
               phase: str = "data_wait") -> Iterator:
    """Yield from `iterable`, attributing each next() wait to `phase` —
    the grouped-dispatch paths' data-wait instrumentation (the prefetcher
    self-times on the k == 1 paths)."""
    it = iter(iterable)
    while True:
        try:
            with profiler.phase(phase):
                item = next(it)
        except StopIteration:
            return
        yield item


# ---------------------------------------------------------------------- #
# process singleton (worker/cohort/prefetcher all feed the same profile)

_PROFILER: Optional[StepProfiler] = None
_PROFILER_LOCK = threading.Lock()


def get_profiler() -> StepProfiler:
    global _PROFILER
    with _PROFILER_LOCK:
        if _PROFILER is None:
            from elasticdl_tpu.observability import goodput

            # the process profiler tees phase adds into the process
            # goodput ledger: one instrumentation site, two consumers
            _PROFILER = StepProfiler(ledger=goodput.get_ledger())
        return _PROFILER


def reset_for_tests() -> None:
    global _PROFILER
    with _PROFILER_LOCK:
        _PROFILER = None
