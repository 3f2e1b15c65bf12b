"""Percent of the traced window with no operation on the device, bulk cells."""
from bench.readers import device_idle_share as read  # noqa: F401
