"""queue_wait_p95_ms.throughput (ms): ``queue_wait_p95_ms`` in cells that
report qps rather than a latency tail."""
from readings import queue_wait_p95_ms as read  # noqa: F401
