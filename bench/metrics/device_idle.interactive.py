"""device_idle.interactive (%): share of the traced window in which no
operation ran on the device, in the latency cells."""
from readings import device_idle_pct as read  # noqa: F401
