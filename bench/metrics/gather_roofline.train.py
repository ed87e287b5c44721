"""Least time of the real ids' forward gather-reduce over the device time under the emb_lookup scope."""
from bench.readers import gather_roofline_pct as read  # noqa: F401
