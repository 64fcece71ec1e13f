"""queue_wait_p95_ms (ms): 95th percentile of the program's queue_wait
spans (admission to the start of the batch's scoring), in the latency
cells."""
from readings import queue_wait_p95_ms as read  # noqa: F401
