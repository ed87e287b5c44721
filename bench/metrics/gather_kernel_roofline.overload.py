"""Least time of the real ids' gather-reduce over the device time under the gather_reduce scope (the kernel, without the relayout)."""
from bench.program_trace import gather_kernel_roofline_pct as read  # noqa: F401
