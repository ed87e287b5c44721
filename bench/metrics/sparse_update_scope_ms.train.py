"""Device ms per step under the sparse_update scope: row gradients and the row-wise Adagrad scatter."""
from bench.program_trace import sparse_update_ms_per_step as read  # noqa: F401
