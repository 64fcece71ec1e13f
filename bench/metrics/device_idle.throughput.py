"""device_idle.throughput (%): share of the traced window in which no
operation ran on the device, in the throughput cells."""
from readings import device_idle_pct as read  # noqa: F401
