"""layer: launcher and master. Job log stamps: launcher start to the first
`training task` completion line."""


def read(run):
    return (run.get("job") or {}).get("first_step_s")
