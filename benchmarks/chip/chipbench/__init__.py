"""The on-chip serving benchmark's harness (see ``benchmarks/chip/run.py``)."""
