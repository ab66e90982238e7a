"""`memory_stats()["peak_bytes_in_use"]` of the fullest device, read after the
window and before the reference runs."""


def read(run):
    return run["memory_peak_bytes"] / 2**30 if run["memory_peak_bytes"] else None
