"""Images per second times operations per image over the MXU peak, percent."""
from bench.readers import mfu as read  # noqa: F401
