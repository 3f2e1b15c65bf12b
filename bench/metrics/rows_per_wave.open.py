"""Mean rows per wave the scheduler cut, open-loop cells."""
from bench.readers import rows_per_wave as read  # noqa: F401
