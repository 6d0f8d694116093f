"""Device idle share of the traced stretch of a twin run: 1 - busy/window,
busy the union of the device's op intervals."""
from bench.readings import device_idle as read  # noqa: F401
