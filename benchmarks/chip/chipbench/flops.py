"""What a serving dispatch requires, as the harness records it.

The counts themselves belong to a model family: the reference file a
configuration names (``references/<reference>.py``) exports
``extend_work(model, dtype, offsets, lengths, completes)`` and
``decode_work(model, dtype, positions)``, each returning a
:class:`DispatchWork`, and the recorder calls them on every dispatch.
They follow logical shapes only, never how a kernel lays the work out,
so a layout change cannot inflate a share of a peak.
"""

from __future__ import annotations

from dataclasses import dataclass

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


@dataclass
class DispatchWork:
    """What one dispatch requires: the model's operations, and the paged
    attention kernel's operations and bytes, summed over layers."""

    tokens: int = 0  # query tokens computed for real rows
    model_flops: float = 0.0
    attn_flops: float = 0.0
    attn_bytes: float = 0.0

    def attn_least_s(self, matmul_flops_per_s: float, bytes_per_s: float) -> float:
        """Least time the attention kernel could take on a chip with these
        peaks: the larger of the compute and the memory bound."""
        return max(self.attn_flops / matmul_flops_per_s, self.attn_bytes / bytes_per_s)
