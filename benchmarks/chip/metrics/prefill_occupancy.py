"""Scheduler: prompt tokens the prefill dispatches carried (their
``serve.dispatch.prefill`` spans' ``tokens``) over the max_batch x
prefill_chunk slots they offered, in the traced part."""

from chipbench.program_spans import prefill_occupancy as read  # noqa: F401
