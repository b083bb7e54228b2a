"""layer: launcher and master. The launcher process's own start to the worker's
`main()`: `start.launch` (interpreter, imports, the master built and serving)
and `start.spawn` up to the end of the worker's `start.process`
(benchmark/start_spans.py)."""

from benchmark import start_spans


def read(run):
    return start_spans.read(run, "start_process_s")
