"""prune_syncs_per_batch.throughput (count): device-to-host read-backs
the pruned executor waited on, per pruned micro-batch of the window: the
program's prune_syncs counter as each batch's prune span carries it."""
from layerspans import window_batches


def read(run):
    per = [s.tags["syncs"] for spans in window_batches(run) for s in spans
           if s.name == "prune" and "syncs" in s.tags]
    return sum(per) / len(per) if per else None
