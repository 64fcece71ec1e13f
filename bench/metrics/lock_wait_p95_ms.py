"""lock_wait_p95_ms (ms): 95th percentile of the program's lock_wait
spans (asking for the serving loop's lock until holding it), in the
latency cells."""
from layerspans import lock_wait_p95_ms as read  # noqa: F401
