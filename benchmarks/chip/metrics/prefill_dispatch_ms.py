"""Executor: device time per fused prefill dispatch in the traced part."""

from chipbench.readers import dispatch_ms


def read(run):
    return dispatch_ms(run, "prefill")
