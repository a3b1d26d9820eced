"""Output tokens emitted in the window over the window, on the host clock."""

from chipbench.readers import output_tokens_per_s as read  # noqa: F401
