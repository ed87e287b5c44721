"""Device ms per batch under the relayout scope: ragged ids turned into the (bags, max_l) matrix."""
from bench.program_trace import relayout_ms_per_batch as read  # noqa: F401
