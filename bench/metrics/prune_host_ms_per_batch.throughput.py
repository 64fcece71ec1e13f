"""prune_host_ms_per_batch.throughput (ms): host time of the pruned
executor per pruned micro-batch of the window: hashing and term order
(prune_plan), each (chunk, shard) visit's row gather and staging
(prune_gather) and kernel call (prune_dispatch); read-backs excluded."""
from layerspans import ms_per_batch


def read(run):
    return ms_per_batch(run, "prune",
                        ("prune_plan", "prune_gather", "prune_dispatch"))
