"""Weights made from the run's seed, by path, on the device.

One function decides every weight: the leaf at path ``p`` with shape
``s`` is ``normal(fold_in(key(seed), crc32(p)), s) * scale(p)``, or ones
for a norm's gain.  The program's weights are made in the pytree layout
the program asks for (``jax.eval_shape`` of its own ``init``), in the type
they are served in, in one jitted call; the reference makes its own copy
from its own table of paths and shapes.  Where the program's layout
differs from the reference's, a leaf gets other numbers and the
comparison that decides ``correct`` fails.

Scales follow the usual initialisation of such models: embeddings and
learned positions at 0.02, projections at ``1 / sqrt(fan_in)``, and the
two projections that write back into the residual stream further by
``1 / sqrt(2 n_layers)``.  Norm gains are ones; biases (a LayerNorm's,
the q/k/v projections') are drawn at 0.1, not zeros, so that the
comparison sees a program that drops one.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

NORMS = ("ln1", "ln2", "ln_f")
BIAS = 0.1


def _scale(path: tuple, shape: tuple, model: dict):
    """Standard deviation of the leaf at ``path``; ``"ones"`` for a norm's gain."""
    name, parent = path[-1], (path[-2] if len(path) > 1 else "")
    if parent in NORMS:
        return {"w": "ones", "b": BIAS}[name]
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    resid = 1.0 / math.sqrt(2 * model["n_layers"])
    if path in (("embed",), ("pos",)):
        return 0.02
    if path == ("lm_head",):
        return 1.0 / math.sqrt(fan_in)
    if parent == "attn" and name in ("wq", "wk", "wv"):
        return 1.0 / math.sqrt(fan_in)
    if parent == "attn" and name in ("bq", "bk", "bv"):
        return BIAS
    if parent in ("attn", "mlp") and name == "wo":
        return resid / math.sqrt(fan_in)
    if parent == "mlp" and name in ("wi", "wg"):
        return 1.0 / math.sqrt(fan_in)
    raise KeyError(f"no initialisation rule for weight {'/'.join(path)}")


def seed_words(seed: int) -> jnp.ndarray:
    """A seed of any size as two uint32 words (the driver's seeds exceed
    32 signed bits)."""
    return jnp.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], jnp.uint32)


def make(spec: dict, model: dict, seed: int, dtype=jnp.float32) -> dict:
    """Nested dict of weights for ``spec`` (``{path tuple: shape}``)."""
    items = sorted(spec.items())
    scales = [_scale(p, tuple(s), model) for p, s in items]

    @jax.jit
    def build(words):
        key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
        out = []
        for (path, shape), sc in zip(items, scales):
            shape = tuple(shape)
            if sc == "ones":
                x = jnp.ones(shape, dtype)
            else:
                k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
                x = (jax.random.normal(k, shape, jnp.float32) * sc).astype(dtype)
            out.append(x)
        return out

    tree: dict = {}
    for (path, _), leaf in zip(items, build(seed_words(seed))):
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf
    return tree


def spec_of(tree) -> dict:
    """``{path: shape}`` of a pytree of arrays or shape structs (dict keys only)."""
    out = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[tuple(k.key for k in kp)] = tuple(leaf.shape)
    return out
