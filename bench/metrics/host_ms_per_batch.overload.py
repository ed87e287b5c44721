"""Mean of the engine's own dispatch and settle span times per batch."""
from bench.readers import host_ms_per_batch as read  # noqa: F401
