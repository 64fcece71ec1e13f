"""readback_copies_per_batch.interactive (count): device-to-host
transfers a scored micro-batch waited on to bring its scores to the
host, per batch of the window: the copies tag of each batch's readback
span. A program that does not tag the span gives nothing to read."""
from layerspans import window_batches


def read(run):
    per = [sum(copies) for copies in (
        [s.tags["copies"] for s in spans
         if s.name == "readback" and "copies" in s.tags]
        for spans in window_batches(run)) if copies]
    return sum(per) / len(per) if per else None
