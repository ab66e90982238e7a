"""Mean `blob_bytes` of the window's `burst.flush` spans, in KiB: host-to-device
bytes a burst, bucket padding included."""

from layers._program_record import mean_counter


def read(run):
    value = mean_counter(run, "burst.flush", "blob_bytes")
    return None if value is None else value / 1024.0
