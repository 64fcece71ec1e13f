"""host_ms_per_batch.interactive (ms): host planning and selection per
scored micro-batch: its plan and dedup_plan spans once, plus the select
span of each of its requests, averaged over the traced run's batches."""
import readings


def read(run):
    per_batch = []
    for traces in readings.batches(run):
        first = traces[0].spans()
        ms = sum(s.duration_s for s in first
                 if s.name in ("plan", "dedup_plan"))
        ms += sum(s.duration_s for t in traces for s in t.spans()
                  if s.name == "select")
        per_batch.append(ms * 1e3)
    return sum(per_batch) / len(per_batch) if per_batch else None
