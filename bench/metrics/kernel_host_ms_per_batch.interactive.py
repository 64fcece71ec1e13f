"""kernel_host_ms_per_batch.interactive (ms): host time inside a scored
micro-batch's kernel_score span spent getting shard tiles (tile_get) and
enqueueing the jitted scoring calls (dispatch), per batch of the window."""
from layerspans import ms_per_batch


def read(run):
    return ms_per_batch(run, "kernel_score", ("tile_get", "dispatch"))
