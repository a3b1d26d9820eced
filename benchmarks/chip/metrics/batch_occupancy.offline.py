"""Scheduler: rows that decode per decode dispatch, over max_batch."""

from chipbench.readers import batch_occupancy as read  # noqa: F401
