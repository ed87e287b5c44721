"""Least time of the bottom MLP, interaction and top MLP over the device time under the interaction and mlp scopes."""
from bench.readers import dense_roofline_pct as read  # noqa: F401
