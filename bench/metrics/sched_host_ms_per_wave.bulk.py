"""Host ms per wave in serve() outside the wave executor, bulk cells."""
from bench.readers import sched_host_ms_per_wave as read  # noqa: F401
