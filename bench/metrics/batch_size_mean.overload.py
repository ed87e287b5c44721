"""Mean requests per micro-batch dispatched in the window."""
from bench.readers import batch_size_mean as read  # noqa: F401
