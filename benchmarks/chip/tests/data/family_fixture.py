"""A model family as the harness's discovery tests bring it: a family file
like ``references/dense_transformer.py``, found by name alone.

- ``weight_scale`` covers the leaves of a decoder with latent attention
  (MLA: ``q_down q_up kv_down k_up v_up`` and their norms) and sparse
  experts (a router, stacked experts, shared experts), beside the dense
  ones: the layout of the program's ``deepseek-v2-236b``.
- ``extend_work`` and ``decode_work`` count by a rule of their own, which
  reads only keys every decoder has, and return a ``FamilyWork`` that
  carries the arguments it was computed from, so that a test can tell
  these counts from any other family's and recompute each one.
- ``tiny(model)`` holds a configuration at tiny widths.

It has no ``Reference``: a run with this family does not check outputs.
"""

import math
from dataclasses import dataclass

from chipbench.flops import DispatchWork

NORMS = ("ln1", "ln2", "ln_f", "q_norm", "kv_norm")
INPUT_PROJ = ("wq", "wk", "wv", "wi", "wg", "q_down", "q_up", "kv_down", "k_up", "v_up", "router")
TINY = dict(n_layers=2, d_model=64, n_heads=4, head_dim=16, d_ff=128, vocab_size=2048)


def tiny(model: dict) -> dict:
    return dict(TINY)


def weight_scale(path: tuple, shape: tuple, model: dict):
    name, parent = path[-1], (path[-2] if len(path) > 1 else "")
    if parent in NORMS:
        return {"w": "ones", "b": 0.1}[name]
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    if path in (("embed",), ("pos",)):
        return 0.02
    if path == ("lm_head",) or name in INPUT_PROJ:
        return 1.0 / math.sqrt(fan_in)
    if name in ("bq", "bk", "bv"):
        return 0.1
    if name == "wo":
        return 1.0 / math.sqrt(2 * model["n_layers"] * fan_in)
    return None


@dataclass
class FamilyWork(DispatchWork):
    inputs: tuple = ()


def _per_token(model: dict) -> float:
    d = model["d_model"]
    return 2.0 * (model["n_layers"] * 12 * d * d + d * model["vocab_size"])


def extend_work(model: dict, dtype: str, offsets, lengths, completes) -> FamilyWork:
    n = sum(int(x) for x in lengths)
    ctx = sum(int(o) + int(x) for o, x in zip(offsets, lengths) if int(x) > 0)
    return FamilyWork(n, _per_token(model) * n, 4.0 * model["d_model"] * ctx, 8.0 * ctx,
                      inputs=(model, dtype, list(offsets), list(lengths), list(completes)))


def decode_work(model: dict, dtype: str, positions) -> FamilyWork:
    rows = len(positions)
    ctx = sum(int(p) + 1 for p in positions)
    return FamilyWork(rows, _per_token(model) * rows, 4.0 * model["d_model"] * ctx, 8.0 * ctx,
                      inputs=(model, dtype, list(positions)))
