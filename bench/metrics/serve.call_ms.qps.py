"""Mean host-clock time of one ``BatchedSearcher.search`` call (ms)."""
from bench.readers import mean_call_ms as read  # noqa: F401
