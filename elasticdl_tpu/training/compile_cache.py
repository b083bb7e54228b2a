"""Rescale fast path, part 1: the executable cache + speculative compiler.

BENCH_r05 measured `compile_and_first_group_s = 88.78s` against
`seconds_to_auc = 30.98s` — compilation is ~3x the useful work, and because
elasticity is re-formation (parallel/elastic.py: "XLA's world is static per
initialize()"), every membership change pays that bill again. This module
makes the recompile avoidable at three layers:

1. `CompileCache`: a process-global, thread-safe store of jitted callables
   and AOT-compiled executables, keyed by (program token, program kind,
   mesh fingerprint, trainer knobs). The token identifies the PROGRAM the
   job's config lowers to — deliberately world-version-independent, so a
   Trainer rebuilt after a re-formation (same job, same mesh shape) gets
   the previous generation's callable back instead of re-tracing. Counters
   (hits/misses/speculative) feed the bench's `recompile_hit_rate`.

2. The persistent on-disk XLA cache (common/runtime.configure_jax_runtime,
   `JAX_COMPILATION_CACHE_DIR` / `--compilation_cache_dir`): covers the
   case the in-memory cache cannot — a re-formed PROCESS. The relaunched
   generation re-traces but deserializes executables instead of compiling.

3. `SpeculativeCompiler`: once a job reaches steady state, a background
   thread precompiles the step programs for the NEIGHBOR world sizes
   (N-1, N+1, plus any size announced through the master's pending-
   membership signal file — common/membership_signal.py), so when the
   resize actually lands the executable is already in both caches and
   recovery is bounded by state movement, not XLA.

Keying note: the default token is unique per Trainer instance (safe: no
cross-trainer sharing for ad-hoc trainers whose loss/optimizer closures
cannot be fingerprinted). Job entrypoints pass `job_cache_token(cfg)` —
derived from the config that fully determines the program — which is what
makes pre/post-resize trainers, and the speculative compiler's throwaway
neighbor trainers, share entries.
"""

from __future__ import annotations

import itertools
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from elasticdl_tpu.common import membership_signal
from elasticdl_tpu.common.log_utils import default_logger
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.registry import default_registry

logger = default_logger(__name__)

_instance_tokens = itertools.count()

#: default LRU capacity; an evicted entry just recompiles on next use
DEFAULT_MAX_ENTRIES = 128


def job_cache_token(cfg) -> str:
    """Program-identity token from a JobConfig: every field that changes
    the traced program is included; nothing world/membership-scoped is.
    Two processes (or two generations) with the same job config produce
    the same token — that is the whole point."""
    return "|".join(
        str(part)
        for part in (
            cfg.model_zoo,
            cfg.model_def,
            sorted(cfg.model_params.items()),
            cfg.loss,
            cfg.optimizer,
            cfg.eval_metrics_fn,
            cfg.param_dtype,
            cfg.compute_dtype,
        )
    )


def instance_token() -> str:
    """Fallback token for trainers built outside a job config: unique per
    call, so entries are private to that trainer (identical semantics to
    the pre-cache lazy build — no false sharing between ad-hoc specs)."""
    return f"~instance-{next(_instance_tokens)}"


def mesh_fingerprint(mesh) -> Tuple:
    """World-version-independent mesh identity: axis layout plus the flat
    device ids. Two Mesh objects over the same devices in the same layout
    fingerprint equal (same-size re-formation reuses executables); a
    resized mesh differs (no stale-shape reuse)."""
    return (
        tuple(str(a) for a in mesh.axis_names),
        tuple(int(s) for s in mesh.devices.shape),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


def aval_signature(tree: Any) -> Tuple:
    """Hashable (shape, dtype) signature of a pytree's array leaves —
    identifies the XLA program a (state, batch) pair lowers to."""
    import jax

    return tuple(
        (tuple(leaf.shape), str(leaf.dtype))
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "shape")
    )


class CompileCache:
    """Thread-safe LRU of compiled program artifacts.

    Two entry classes share the store:
    - jitted callables (`get_or_build`): counted — a hit here is a resize
      that did NOT re-trace; `stats()["hit_rate"]` is the bench's
      `recompile_hit_rate`.
    - AOT executables (`store_aot` / `peek`): uncounted lookups (they sit
      in front of a callable that was already counted once), tallied only
      as `speculative_compiles` when marked so.
    """

    def __init__(self, max_entries: int = DEFAULT_MAX_ENTRIES):
        self.max_entries = max(1, int(max_entries))
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Tuple, Any]" = OrderedDict()  # guarded_by: _lock
        self._hits = 0          # guarded_by: _lock
        self._misses = 0        # guarded_by: _lock
        self._speculative = 0   # guarded_by: _lock
        # bumped on every store_aot: dispatchers pin a negative AOT lookup
        # and re-check only when this moves (zero per-step tree walks in
        # the no-AOT common case) — see Trainer._dispatch
        self._aot_generation = 0  # guarded_by: _lock

    # ------------------------------------------------------------------ #

    def get_or_build(
        self, key: Tuple, build: Callable[[], Any], *, speculative: bool = False
    ) -> Any:
        """Return the cached value for `key`, building (OUTSIDE the lock —
        builds are multi-second compiles) on a miss. A lost build race keeps
        the first value. `speculative=True` marks a background precompile:
        a resulting insert counts as speculative, not as a (real) miss."""
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                if not speculative:
                    self._hits += 1
                return self._entries[key]
        value = build()
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return self._entries[key]
            self._entries[key] = value
            if speculative:
                self._speculative += 1
            else:
                self._misses += 1
            while len(self._entries) > self.max_entries:
                evicted, _ = self._entries.popitem(last=False)
                logger.info("compile cache evicted %r (LRU)", evicted[:2])
            return value

    def peek(self, key: Tuple) -> Optional[Any]:
        """Uncounted lookup (AOT executables in front of a counted
        callable); refreshes LRU position on a find."""
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return self._entries[key]
            return None

    def store_aot(self, key: Tuple, value: Any, *, speculative: bool = False) -> Any:
        """Insert an AOT-compiled executable; first writer wins."""
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return self._entries[key]
            self._entries[key] = value
            self._aot_generation += 1
            if speculative:
                self._speculative += 1
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
            return value

    @property
    def aot_generation(self) -> int:
        with self._lock:
            return self._aot_generation

    def contains(self, key: Tuple) -> bool:
        with self._lock:
            return key in self._entries

    # ------------------------------------------------------------------ #

    def stats(self) -> Dict[str, float]:
        with self._lock:
            lookups = self._hits + self._misses
            return {
                "hits": self._hits,
                "misses": self._misses,
                "speculative_compiles": self._speculative,
                "entries": len(self._entries),
                "hit_rate": (self._hits / lookups) if lookups else 0.0,
            }

    def reset_stats(self) -> None:
        with self._lock:
            self._hits = self._misses = self._speculative = 0

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._hits = self._misses = self._speculative = 0


_GLOBAL_CACHE = CompileCache()


def global_cache() -> CompileCache:
    """The process-wide cache every job-entrypoint Trainer shares."""
    return _GLOBAL_CACHE


# Scrape surface for the PROCESS-GLOBAL cache (the one job entrypoints and
# the speculative compiler share); ad-hoc trainers' private caches are
# deliberately not aggregated — their stats describe nothing cross-resize.
_reg = default_registry()
for _stat, _help in (
    ("hits", "executable-cache hits (a resize that did NOT re-trace)"),
    ("misses", "executable-cache misses (real re-traces)"),
    ("speculative_compiles", "background neighbor-size precompiles"),
    ("entries", "live cache entries"),
    ("hit_rate", "hits / (hits + misses) — the bench's recompile_hit_rate"),
):
    _reg.gauge(
        f"edl_compile_cache_{_stat}", _help
    ).set_fn(lambda s=_stat: _GLOBAL_CACHE.stats()[s])


# ---------------------------------------------------------------------- #
# speculative neighbor-world compilation


class SpeculativeCompiler:
    """Background precompilation of the step programs for neighbor world
    sizes, so a resize lands on a warm cache.

    `compile_for_size(size)` does the actual work — the caller supplies it
    (typically: build a throwaway Trainer on the neighbor-size mesh against
    the SHARED CompileCache/token and AOT-compile its steps). It may raise
    `SkipSize` for sizes this process cannot represent (e.g. scale-up
    beyond the visible devices: on real multi-host TPU the devices of a
    larger world do not exist yet, and the persistent on-disk cache is the
    warmth mechanism there instead). Failures are logged, never raised into
    the training thread; a size is compiled at most once until the
    candidate set changes.

    Candidates: current±1 plus `extra_sizes` plus whatever the master's
    pending-membership signal file currently announces. The announced size
    is compiled FIRST — it is the one that is actually about to happen.
    """

    def __init__(
        self,
        compile_for_size: Callable[[int], Any],
        current_size: int,
        *,
        min_size: int = 1,
        max_size: Optional[int] = None,
        signal_path: str = "",
        extra_sizes: Sequence[int] = (),
        poll_s: float = 2.0,
    ):
        self._compile_for_size = compile_for_size
        self.current_size = int(current_size)
        self.min_size = int(min_size)
        self.max_size = max_size
        self.signal_path = signal_path
        self.extra_sizes = tuple(int(s) for s in extra_sizes)
        self.poll_s = poll_s
        self._done: set = set()        # guarded_by: _lock
        self._failed: set = set()      # guarded_by: _lock
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    class SkipSize(Exception):
        """compile_for_size: this size is not representable here (not an
        error — e.g. scale-up past the visible device set)."""

    def candidate_sizes(self) -> List[int]:
        # ONE implementation of the candidate policy (announced size
        # first, then nearest neighbors): parallel/elastic.py owns it;
        # imported lazily so this module stays importable without jax
        from elasticdl_tpu.parallel.elastic import neighbor_world_sizes

        pending = membership_signal.pending_size(self.signal_path or None)
        sizes = set(
            neighbor_world_sizes(
                self.current_size, pending=pending,
                min_size=self.min_size, max_size=self.max_size,
            )
        )
        sizes.update(
            s for s in self.extra_sizes
            if s >= self.min_size
            and (self.max_size is None or s <= self.max_size)
            and s != self.current_size
        )
        return sorted(
            sizes, key=lambda s: (s != pending, abs(s - self.current_size), s)
        )

    def precompile_once(self) -> List[int]:
        """One pass over the current candidates; returns sizes compiled
        this pass. Synchronous — tests and the bench call this directly;
        `start()` loops it on a daemon thread."""
        compiled = []
        for size in self.candidate_sizes():
            with self._lock:
                if size in self._done or size in self._failed:
                    continue
            if self._stop.is_set():
                break
            try:
                with tracing.span(
                    "compile.speculative", size=size,
                    current_size=self.current_size,
                ) as sp:
                    try:
                        self._compile_for_size(size)
                    except SpeculativeCompiler.SkipSize:
                        sp.set(outcome="skipped")
                        raise
                    sp.set(outcome="compiled")
            except SpeculativeCompiler.SkipSize as e:
                logger.info("speculative compile skipped size %d: %s", size, e)
                with self._lock:
                    self._failed.add(size)
            except Exception:
                logger.exception("speculative compile failed for size %d", size)
                with self._lock:
                    self._failed.add(size)
            else:
                logger.info("speculative compile ready for world size %d", size)
                with self._lock:
                    self._done.add(size)
                compiled.append(size)
        return compiled

    def notify_resize(self, new_size: int) -> None:
        """The world actually resized: neighbors move with it (previously
        failed sizes may become representable, so both sets reset)."""
        with self._lock:
            self.current_size = int(new_size)
            self._done.clear()
            self._failed.clear()

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._loop, name="edl-speculative-compile", daemon=True
        )
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.precompile_once()
            except Exception:
                logger.exception("speculative compile pass failed")
            self._stop.wait(self.poll_s)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
