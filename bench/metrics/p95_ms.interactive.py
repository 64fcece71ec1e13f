"""p95_ms.interactive (ms): 95th percentile, by nearest rank, of every request of the
window, timed at the client from the moment it was due. A request that
failed or never came back counts as late as the run waited for it.
Host clock."""
import readings


def read(run):
    return readings.nearest_rank(readings.latencies_s(run), 0.95) * 1e3
