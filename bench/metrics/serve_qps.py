"""Requests settled in the window per second."""
from bench.readers import completed_qps as read  # noqa: F401
