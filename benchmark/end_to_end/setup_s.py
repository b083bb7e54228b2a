"""Process start to the start of the measured window. Host clock."""


def read(run):
    return run["setup_s"]
