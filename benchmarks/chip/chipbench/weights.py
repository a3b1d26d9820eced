"""Weights made from the run's seed, by path, on the device.

One function decides every weight: the leaf at path ``p`` with shape
``s`` is ``normal(fold_in(key(seed), crc32(p)), s) * scale``, or ones
for a norm's gain, where ``scale`` is what the model family's rule gives
for ``(p, s)``.  The rule is ``weight_scale(path, shape, model)`` of the
reference file a configuration names (``references/<reference>.py``),
which is the one place that knows the family's leaves.  The program's
weights are made in the pytree layout the program asks for
(``jax.eval_shape`` of its own ``init``), in the type they are served
in, in one jitted call; the reference makes its own copy from its own
table of paths and shapes.  Where the program's layout differs from the
reference's, a leaf gets other numbers and the comparison that decides
``correct`` fails.
"""

from __future__ import annotations

import inspect
import zlib
from typing import Callable

import jax
import jax.numpy as jnp


def seed_words(seed: int) -> jnp.ndarray:
    """A seed of any size as two uint32 words (the driver's seeds exceed
    32 signed bits)."""
    return jnp.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], jnp.uint32)


def make(spec: dict, model: dict, seed: int, dtype, scale: Callable) -> dict:
    """Nested dict of weights for ``spec`` (``{path tuple: shape}``), each
    leaf drawn at ``scale(path, shape, model)``: a standard deviation,
    ``"ones"``, or ``None`` for a leaf the rule does not cover, which is
    an error."""
    items = sorted(spec.items())
    scales = []
    for p, s in items:
        sc = scale(p, tuple(s), model)
        if sc is None:
            raise KeyError(f"no initialisation rule for weight {'/'.join(p)}: "
                           f"add it to {scale.__name__} in {inspect.getfile(scale)}")
        scales.append(sc)

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
