"""99th percentile over every query of the window of the time from its due
time to the return of its call (ms)."""
from functools import partial

from bench.readers import latency_ms

read = partial(latency_ms, q=99)
