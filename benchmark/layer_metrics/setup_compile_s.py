"""layer: trainer. The program's own spans: the seconds under the `compile` spans
that end before the window — `Trainer`'s AOT compilations and the first
dispatch of every program nobody compiled ahead: trace, lower, the backend's
compile or the persistent cache's load (benchmark/start_spans.py)."""

from benchmark import start_spans


def read(run):
    return start_spans.read(run, "setup_compile_s")
