"""Samples of the window's steps per second of those steps."""
from bench.readers import samples_per_s as read  # noqa: F401
