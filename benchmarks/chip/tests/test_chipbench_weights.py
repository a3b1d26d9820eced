"""Granite's weights are the numbers they were before the family's rule
moved into its reference file: per-leaf sums of the weights at tiny
widths and a fixed seed, pinned from the tree before the move."""

import numpy as np

import chipbench_tiny as tiny
from chipbench import cells, weights

SEED = 2**31 + 5
# the sum of each leaf's values in float64, at ``chipbench_tiny.shrink``'s widths
GOLDEN = {
    "embed": 1.80739383781318,
    "layers/attn/bk": 0.22244359459728003,
    "layers/attn/bq": 0.6113621073309332,
    "layers/attn/bv": 0.09420633898116648,
    "layers/attn/wk": 3.3360689681358053,
    "layers/attn/wo": -3.628957705166613,
    "layers/attn/wq": -2.6411845901820925,
    "layers/attn/wv": 4.067869262493332,
    "layers/ln1/b": -0.4182447323692031,
    "layers/ln1/w": 128.0,
    "layers/ln2/b": 0.23430589423514903,
    "layers/ln2/w": 128.0,
    "layers/mlp/wi": -24.490999913494306,
    "layers/mlp/wo": -1.8745928269754586,
    "lm_head": -83.42183497855501,
    "ln_f/b": 0.9977314579300582,
    "ln_f/w": 64.0,
    "pos": -1.3684387130449096,
}


def test_granite_weights_are_unchanged():
    import jax
    import jax.numpy as jnp

    cell = tiny.shrink(cells.load("granite34b-l4-decode-offline"))
    model = cell.config["model"]
    family = cells.reference_module(cell.config["reference"], cell.bench_dir)
    spec = {tuple(k): tuple(v) for k, v in family.weight_spec(model).items()}
    w = weights.make(spec, model, SEED, jnp.float32, family.weight_scale)
    sums = {"/".join(k.key for k in kp): float(np.asarray(x, np.float64).sum())
            for kp, x in jax.tree_util.tree_flatten_with_path(w)[0]}
    assert sums == GOLDEN
