"""layer: device. `device.memory_stats()["peak_bytes_in_use"]` after the
window on the fullest chip (job cells: the worker's own gauge)."""


def read(run):
    peak = run["device"].get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
