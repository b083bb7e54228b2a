"""layer: worker loop. 1 - sum(K x ms/step of the window's tasks) / wall
between their stamps: the share of the window the worker spent outside its
timed step region (read, parse, lease and report)."""


def read(run):
    return (run.get("job") or {}).get("host_wait_pct")
