"""Device idle share of the traced grid calls, mean over the devices."""
from bench.readings import device_idle as read  # noqa: F401
