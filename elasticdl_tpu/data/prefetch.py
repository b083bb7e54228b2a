"""Async host→device batch prefetching with optional wire compression.

Reference parity: the reference's input path was `tf.data` with internal
prefetching; the rebuild's TaskDataService yields host numpy batches, and on
TPU a synchronous `device_put` per step serializes the host→device transfer
with the compute. Measured on this sandbox's v5e chip (DeepFM, batch 8192,
160B/sample): ~5.6M samples/s with blocking per-step transfers, ~6.2M with
lookahead, against a ~6.5M pure-transfer ceiling — the link, not the math,
bounds the step. A threaded producer measured *slower* (4.9M) than the
main-thread lookahead: `device_put` dispatch contends on the GIL with the
step dispatch, so this implementation keeps everything on the calling thread
and relies on JAX's async dispatch — `device_put` returns before the copy
completes, letting up to `depth` transfers ride behind the running step.

Wire compression (`cast="bfloat16"`): float32/float64 leaves are cast to
bfloat16 on the host before transfer, halving float bytes on the wire. When
the model's compute dtype is bfloat16 (the TPU default here), the values are
cast there anyway, so the computation sees identical inputs.

Elasticity (rescale fast path): in-flight device batches carry the OLD
mesh's shardings across a re-formation, so the prefetcher keeps each
pending batch's HOST copy alongside the device copy and exposes `drain()`
— the worker calls it on reform/rescale, gets the pending host batches
back, and requeues them through the new mesh instead of silently dropping
them (exactly-once accounting is span-based, so a dropped-but-uncounted
batch would be re-read anyway after a full teardown — but an IN-PLACE
rescale has no teardown, and without the drain those records would be
lost from the task's span).

`depth` and `cast` resolve from the environment when not given:
`EDL_PREFETCH_DEPTH` (default 2) and `EDL_PREFETCH_CAST` (default "") —
so deployments can tune the lookahead window without a config/argv change.
"""

from __future__ import annotations

import os
from collections import deque
from typing import Any, Iterable, Iterator, List, Optional

import numpy as np

from elasticdl_tpu.common.log_utils import default_logger
from elasticdl_tpu.observability import profile as profile_lib
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.registry import default_registry
from elasticdl_tpu.parallel import mesh as mesh_lib

logger = default_logger(__name__)

DEFAULT_DEPTH = 2

# prefetch telemetry: batch flow + drain accounting (a drain is the
# rescale-path event — its batch count is how much lookahead a resize
# had to requeue). The depth gauge tracks the most recent prefetcher's
# configured lookahead (one live prefetcher per worker in practice).
_reg = default_registry()
_PF_BATCHES = _reg.counter(
    "edl_prefetch_batches_total", "device batches served to the step loop")
_PF_DRAINS = _reg.counter(
    "edl_prefetch_drains_total", "drain() calls (reform/rescale requeues)")
_PF_DRAINED_BATCHES = _reg.counter(
    "edl_prefetch_drained_batches_total",
    "pending host batches handed back by drains")
_PF_DEPTH = _reg.gauge(
    "edl_prefetch_depth", "configured lookahead of the latest prefetcher")


def resolve_depth(depth: Optional[int]) -> int:
    """None -> EDL_PREFETCH_DEPTH -> default; explicit values win."""
    if depth is not None:
        return int(depth)
    try:
        return int(os.environ.get("EDL_PREFETCH_DEPTH", DEFAULT_DEPTH))
    except ValueError:
        return DEFAULT_DEPTH


def resolve_cast(cast: Optional[str]) -> str:
    """None -> EDL_PREFETCH_CAST -> no cast; explicit values win."""
    if cast is not None:
        return cast
    return os.environ.get("EDL_PREFETCH_CAST", "")


def _wire_cast(batch: Any, cast: str) -> Any:
    if not cast:
        return batch
    import jax
    import ml_dtypes

    wire = np.dtype(ml_dtypes.bfloat16) if cast == "bfloat16" else np.dtype(cast)

    def conv(x):
        if isinstance(x, np.ndarray) and x.dtype in (np.float32, np.float64):
            return x.astype(wire)
        return x

    # "mask" stays float32: the worker SUMS it for record accounting, and
    # bf16 addition is exact only up to 256 — a cast mask would corrupt
    # records_done and with it the exactly-once protocol.
    out = dict(batch)
    for k, v in out.items():
        if k == "mask":
            continue
        out[k] = jax.tree_util.tree_map(conv, v)
    return out


class DevicePrefetcher:
    """Iterator of device-resident (batch-sharded) batches keeping up to
    `depth` transfers in flight ahead of the consumer, with an explicit
    `drain()` for elastic re-formation. depth<=0 disables lookahead but
    still device-puts (and wire-casts) each batch.

    Each pending slot holds (host_batch, device_batch): the host copy costs
    no extra materialization (the source yields host batches anyway) and is
    what `drain()` hands back for requeueing — the device copies are
    dropped, since their shardings die with the old mesh.
    """

    def __init__(
        self,
        mesh,
        batches: Iterable[Any],
        depth: Optional[int] = None,
        cast: Optional[str] = None,
        partition=None,
    ):
        self._mesh = mesh
        self.source: Iterator[Any] = iter(batches)
        self.depth = resolve_depth(depth)
        self.cast = resolve_cast(cast)
        self._partition = partition
        self._buf: deque = deque()   # (host_batch, device_batch)
        self._exhausted = False
        self._drained = False
        _PF_DEPTH.set(self.depth)

    def _put(self, host_batch):
        # h2d attribution (observability/profile.py): the cast + sharded
        # device_put dispatch is the transfer half of the input path. The
        # phase is two perf_counter reads, a float add and an annotation —
        # cheap enough for the always-on contract.
        with profile_lib.get_profiler().phase("h2d"):
            return mesh_lib.shard_batch(
                self._mesh, _wire_cast(host_batch, self.cast), self._partition
            )

    def _fill(self) -> None:
        prof = profile_lib.get_profiler()
        while not self._exhausted and len(self._buf) < max(1, self.depth):
            try:
                # blocking on the reader/parse pipeline IS the data wait
                with prof.phase("data_wait"):
                    host = next(self.source)
            except StopIteration:
                self._exhausted = True
                return
            self._buf.append((host, self._put(host)))

    def __iter__(self) -> "DevicePrefetcher":
        return self

    def __next__(self):
        if self._drained:
            raise StopIteration
        if self.depth <= 0:
            with profile_lib.get_profiler().phase("data_wait"):
                host = next(self.source)
            _PF_BATCHES.inc()
            return self._put(host)
        self._fill()
        if not self._buf:
            raise StopIteration
        _, device_batch = self._buf.popleft()
        _PF_BATCHES.inc()
        return device_batch

    def drain(self) -> List[Any]:
        """Invalidate the lookahead window: return the pending HOST batches
        (oldest first) and stop this prefetcher. The caller requeues them —
        through a new prefetcher on the new mesh, or back to the task
        service — so no record silently disappears across a re-formation.
        The un-consumed source remains available as `self.source`."""
        with tracing.span("prefetch.drain") as sp:
            pending = [host for host, _ in self._buf]
            self._buf.clear()
            self._drained = True
            sp.set(pending_batches=len(pending))
        _PF_DRAINS.inc()
        _PF_DRAINED_BATCHES.inc(len(pending))
        return pending

    def close(self) -> None:
        """Release the source (generator-based sources stop cleanly)."""
        self._buf.clear()
        self._drained = True
        close = getattr(self.source, "close", None)
        if close is not None:
            close()


def prefetch_to_device(
    mesh, batches: Iterable[Any], depth: Optional[int] = None,
    cast: Optional[str] = None, partition=None,
) -> DevicePrefetcher:
    """Yield device-resident (batch-sharded) batches, keeping up to `depth`
    transfers in flight ahead of the consumer (see DevicePrefetcher; this
    wrapper is the stable entry point call sites use)."""
    return DevicePrefetcher(
        mesh, batches, depth=depth, cast=cast, partition=partition
    )
