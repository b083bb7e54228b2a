"""layer: worker loop. `start.first_task`'s own time up to the first completion
line: the first lease, input, transfer and the first dispatches' run — without
state and compile, which are its children (benchmark/start_spans.py)."""

from benchmark import start_spans


def read(run):
    return start_spans.read(run, "start_first_task_s")
