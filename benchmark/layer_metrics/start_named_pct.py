"""layer: launcher and master. Of the stretch from the launcher's start to the
first task's completion line, the share under any `start.*` span of any
process: the check that the ledger is a partition
(benchmark/start_spans.py)."""

from benchmark import start_spans


def read(run):
    return start_spans.read(run, "start_named_pct")
