"""device_bytes_per_index_byte (B/B): the device's peak bytes in use,
read after the window, over the logical arena bytes: the HBM an index
costs. Read from JAX's memory statistics, before the reference runs."""


def read(run):
    return run.memory_peak_bytes / run.arena_bytes
