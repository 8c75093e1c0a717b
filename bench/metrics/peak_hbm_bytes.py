"""Device: ``memory_stats()["peak_bytes_in_use"]`` after the window."""
UNIT = "bytes"


def read(run):
    return run.memory_peak_bytes
