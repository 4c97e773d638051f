"""Share of the traced window in which no op ran on the device: 1 -
union of device-op intervals / window, averaged over the chips used."""
from bench.readers import device_idle as read  # noqa: F401
