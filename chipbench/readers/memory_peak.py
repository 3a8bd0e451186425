"""Peak device memory in use on the fullest chip of the cell, from
``device.memory_stats()["peak_bytes_in_use"]`` read as the window
closed, in GB (1e9 bytes)."""


def read(params, run):
    peak = run["memory_peak_bytes"]
    return peak / 1e9 if peak else None
