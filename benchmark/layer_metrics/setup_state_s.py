"""layer: trainer. The program's own spans: the seconds under `start.state`
(`Trainer.init_state`: trace, compile or cache load, and the device's run of
the initialisation) and `ckpt.restore`, before the window
(benchmark/start_spans.py)."""

from benchmark import start_spans


def read(run):
    return start_spans.read(run, "setup_state_s")
