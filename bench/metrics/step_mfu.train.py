"""Least time of the window's steps at the chip's peaks over the window."""
from bench.readers import step_mfu_pct as read  # noqa: F401
