"""Share of the traced window in which the device ran no op (%)."""
from bench.readers import idle_share as read  # noqa: F401
