"""Kernels: least time of the paged attention kernel's calls (logical
shapes, published peaks) over its device time in the trace."""

from chipbench.readers import paged_attn_roofline as read  # noqa: F401
