"""Least time of the window's work at the chip's peaks over the device's busy time."""
from bench.readers import step_mfu_pct as read  # noqa: F401
