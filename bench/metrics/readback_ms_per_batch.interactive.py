"""readback_ms_per_batch.interactive (ms): the blocking read-back of a
scored micro-batch's scores to the host (readback, inside kernel_score),
per batch of the window: the wait for the device plus the copy."""
from layerspans import ms_per_batch


def read(run):
    return ms_per_batch(run, "kernel_score", ("readback",))
