"""layer: trainer. The program's compile ledger: the programs JAX compiled and
wrote to the persistent cache (`/jax/compilation_cache/cache_misses`) under
the `compile` and `start.state` spans before the window. 0 in a warm run
(benchmark/start_spans.py)."""

from benchmark import start_spans


def read(run):
    return start_spans.read(run, "setup_cache_misses")
