"""shard_readback_ms_per_batch.sharded (ms): the blocking read-backs of a
sharded batch's per-shard scores to the host (shard_readback, one per
shard dispatch: the wait for the chip plus the copy), summed per batch
of the window. A program whose shard dispatches record no such spans
gives nothing to read."""
from layerspans import ms_per_batch


def read(run):
    return ms_per_batch(run, "scatter", ("shard_readback",))
