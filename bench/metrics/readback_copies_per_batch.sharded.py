"""readback_copies_per_batch.sharded (count): device-to-host transfers a
sharded batch waited on to bring its shards' scores to the host, per
batch of the window: the copies tag of each batch's scatter span. A
program that does not tag the span gives nothing to read."""
from layerspans import window_batches


def read(run):
    per = [s.tags["copies"] for spans in window_batches(run)
           for s in spans if s.name == "scatter" and "copies" in s.tags]
    return sum(per) / len(per) if per else None
