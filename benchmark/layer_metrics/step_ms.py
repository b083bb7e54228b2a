"""layer: trainer. Resident cells: the median dispatch's seconds over its
steps. Job cells: the median of the tasks' own `ms/step` (the worker's timed
step region)."""


def read(run):
    return run["window"].get("step_ms")
