"""Share of device busy time in copies of the whole KV pool, in the cell
judged on latency."""
from benchmark.layer_metrics._pool_copies import share as read  # noqa: F401
