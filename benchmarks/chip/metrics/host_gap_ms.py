"""Executor: device-idle milliseconds inside the engine's ticks
(``serve.step`` spans) per dispatch made in them, in the traced part."""

from chipbench.program_spans import host_gap_ms as read  # noqa: F401
