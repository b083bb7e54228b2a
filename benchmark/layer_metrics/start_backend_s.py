"""layer: worker loop. `start.connect` + `start.backend` + `start.trainer`:
registration, the JAX runtime and the chips up to the `training devices` line,
the model's spec and the `Trainer` (benchmark/start_spans.py)."""

from benchmark import start_spans


def read(run):
    return start_spans.read(run, "start_backend_s")
