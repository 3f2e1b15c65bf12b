"""Host ms per wave in serve() outside the wave executor, open-loop cells."""
from bench.readers import sched_host_ms_per_wave as read  # noqa: F401
