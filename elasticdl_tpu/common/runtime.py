"""Per-process JAX runtime setup shared by every entry point that compiles:
both worker flavors, bench.py, chip_smoke.py and __graft_entry__.py.

Reference analog: none — upstream's TF2 runtime had no compile step to
cache. Here it matters doubly: (1) the first XLA compilation of a real model
on the chip is the largest part of a cold start, and (2) elastic recovery
RELAUNCHES worker processes (process_manager/k8s_instance_manager), so
without a persistent cache every preemption pays the full recompile on top
of restore. With one the relaunched generation deserializes the previous
generation's executables instead.

Where the cache lives — one rule:

1. `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and this module
   calls no `jax.config.update("jax_compilation_cache_dir", ...)` at all.
   Child processes inherit the variable through the environment.
2. else `--compilation_cache_dir` when given,
3. else `<checkout>/.jax_cache`, derived from this package's location.

The directory is part of the cache key, so it is never a temporary name.
"""

from __future__ import annotations

import json
import os

from elasticdl_tpu.common.log_utils import default_logger

logger = default_logger(__name__)

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def compilation_cache_dir(cfg=None) -> str:
    """The directory the persistent cache uses under the rule above."""
    return (
        os.environ.get(CACHE_DIR_ENV)
        or getattr(cfg, "compilation_cache_dir", "")
        or DEFAULT_CACHE_DIR
    )


def configure_jax_runtime(cfg=None) -> str:
    """Apply the JAX process settings. Call before the first compile
    (idempotent; safe to call from every entry point). Returns the cache
    directory in use."""
    import jax

    cache_dir = compilation_cache_dir(cfg)
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    min_compile_s = getattr(cfg, "compilation_cache_min_compile_s", -1.0)
    if min_compile_s >= 0:
        # explicit floor override (tests set 0 so even test-sized
        # programs cache); production keeps JAX's defaults — writing
        # every sub-second jit to shared storage is churn, not savings
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", float(min_compile_s)
        )
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    logger.info("persistent XLA compilation cache at %s", cache_dir)
    return cache_dir


def log_training_devices(mesh) -> None:
    """One line naming what this process trains on, as JAX reports it.
    With `JAX_PLATFORMS` unset a TPU that fails to initialise leaves JAX on
    the CPU with only a warning; this line is how a job's log (and
    chip_smoke.py, which parses it) tells the two apart."""
    devices = mesh.devices.flatten()
    logger.info("training devices: %s", json.dumps({
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": int(devices.size),
        "mesh": dict(zip(mesh.axis_names, map(int, mesh.devices.shape))),
    }))
