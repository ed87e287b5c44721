"""99th percentile of submit time minus due time: how late the load generator ran."""
from bench.readers import gen_lag_p99_ms as read  # noqa: F401
