"""shard_critical_ms_per_batch.sharded (ms): per batch of the window, the
largest over the chips of a chip's summed shard stages (shard_kernel,
shard_readback and shard_select of the dispatches tagged with its
device): the busiest chip's host time, which bounds the batch while a
chip takes one dispatch at a time. A program whose shard dispatches
record no such spans gives nothing to read."""
from layerspans import window_batches

STAGES = ("shard_kernel", "shard_readback", "shard_select")


def read(run):
    per = []
    for spans in window_batches(run):
        by_device: dict = {}
        for s in spans:
            if s.name in STAGES and "device" in s.tags:
                d = s.tags["device"]
                by_device[d] = by_device.get(d, 0.0) + s.duration_s
        if by_device:
            per.append(max(by_device.values()))
    return 1e3 * sum(per) / len(per) if per else None
