"""Model step: the model operations the window required, over the wall time
of the steps that dispatched work, over the chip's matmul peak."""

from chipbench.readers import mfu as read  # noqa: F401
