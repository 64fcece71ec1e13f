"""device_idle.sharded (%): share of the traced window in which no
operation ran on a chip, averaged over the chips of a sharded cell."""
from readings import device_idle_pct as read  # noqa: F401
