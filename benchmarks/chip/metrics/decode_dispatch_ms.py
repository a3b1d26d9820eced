"""Executor: device time per decode dispatch in the traced part."""

from chipbench.readers import dispatch_ms


def read(run):
    return dispatch_ms(run, "decode")
