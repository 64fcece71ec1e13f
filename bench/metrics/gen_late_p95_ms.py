"""gen_late_p95_ms (ms): how late the open-loop generator sent its
requests, 95th percentile of sent - due. Host clock; open loops only."""
import readings


def read(run):
    if run.traffic["loop"] != "open":
        return None
    rec = run.records
    i = rec.issued
    return readings.nearest_rank(rec.sent[i] - rec.due[i], 0.95) * 1e3
