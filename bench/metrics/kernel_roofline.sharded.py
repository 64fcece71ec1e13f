"""kernel_roofline.sharded (%): the scoring kernels' share of the HBM
roofline over the traced window of a sharded cell, its chips together.

Numerator: the least time one chip could take to read what the window's
requests need, the distinct (block, row) pairs of each query over the
whole archive times the 128-byte row, over the table's peak HBM
bandwidth; a batch whose scatter span straddles the trace's edge counts
for the part inside. The count comes from the queries, not from the path
that served them, so no implementation reads above 100%.

Denominator: the device time of the Pallas kernels' events
(``tpu_custom_call``), summed over the chips' planes."""
import readings

KERNELS = r"tpu_custom_call"


def read(run):
    dev = run.device
    if dev is None or run.peak is None:
        return None
    kernel_s = dev.op_seconds(KERNELS)
    if kernel_s <= 0:
        return None
    w0, w1 = dev.window
    index_of = {a.trace_id: i for i, a in enumerate(run.records.answers)
                if a is not None and a.trace_id}
    kmer = run.config["kmer"]
    nbytes = 0.0
    for t in run.traces:
        i = index_of.get(t.trace_id)
        sc = [s for s in t.spans() if s.name == "scatter"]
        if i is None or not sc:
            continue
        inside = sum(max(0.0, min(s.end_s, w1) - max(s.start_s, w0))
                     for s in sc)
        span = sum(s.duration_s for s in sc)
        if inside <= 0 or span <= 0:
            continue
        rows = readings.distinct_rows(run.queries[i].codes,
                                      run.reference.widths, kmer)
        nbytes += rows * readings.ROW_BYTES * inside / span
    return 100.0 * nbytes / float(run.peak["hbm_bytes_per_s"]) / kernel_s
