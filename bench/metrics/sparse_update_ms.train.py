"""Device time per step outside every stage scope: the row gradients and the row-wise Adagrad scatter."""
from bench.readers import outside_stages_ms_per_step as read  # noqa: F401
