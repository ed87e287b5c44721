"""Least time of the real ids' gather-reduce over the device time under the sparse_lookup and emb_lookup scopes."""
from bench.readers import gather_roofline_pct as read  # noqa: F401
