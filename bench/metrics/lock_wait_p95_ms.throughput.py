"""lock_wait_p95_ms.throughput (ms): ``lock_wait_p95_ms`` in cells that
report qps rather than a latency tail."""
from layerspans import lock_wait_p95_ms as read  # noqa: F401
