"""The benchmark's weight draw: every leaf of every preset the program
builds, the Mamba2 per-head leaves in their published ranges, and the
dense tree by the formulas its leaves have always been drawn with."""

import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import weights  # noqa: E402
from repro.configs import ARCHS  # noqa: E402


def _draw(arch, seed=2 ** 31 + 3):
    import jax
    return jax.tree_util.tree_flatten_with_path(
        weights.make_params(arch, jax.random.PRNGKey(seed)))[0]


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_every_preset_draws_every_leaf_finite(name):
    import jax
    arch = ARCHS[name].reduced()
    want = jax.tree_util.tree_flatten_with_path(weights.shapes(arch))[0]
    got = _draw(arch)
    assert [p for p, _ in got] == [p for p, _ in want]
    for (path, leaf), (_, sd) in zip(got, want):
        assert leaf.shape == sd.shape and leaf.dtype == sd.dtype, path
        assert np.isfinite(np.asarray(leaf, np.float32)).all(), path


def test_mamba2_leaves_fall_in_their_published_ranges():
    import jax
    leaves = {weights._leaf_name(p): np.asarray(v, np.float64)
              for p, v in _draw(ARCHS["zamba2-1.2b"].reduced())
              if weights.stacked(p)}
    A = np.exp(leaves["a_log"])
    assert (A >= 1.0).all() and (A <= 16.0).all() and A.std() > 1.0
    dt = np.asarray(jax.nn.softplus(leaves["dt_bias"]))
    assert (dt >= 1e-4 * (1 - 1e-5)).all() and (dt <= 0.1 * (1 + 1e-5)).all()
    assert dt.min() < 3e-3 and dt.max() > 3e-2     # spread over the range
    assert abs(leaves["d_skip"].mean() - 1.0) < 0.1
    assert 0.0 < leaves["d_skip"].std() < 0.2
    # a vector no rule names: the convolution's bias
    assert 0.05 < leaves["conv_b"].std() < 0.15
    # many heads fill each range to its ends: A uniform, dt log-uniform
    key = jax.random.PRNGKey(7)
    A = np.exp(np.asarray(weights.draw_leaf("a_log", key, (8192,),
                                            np.float32), np.float64))
    assert 1.0 <= A.min() < 1.01 and 15.99 < A.max() <= 16.0
    assert abs(A.mean() - 8.5) < 0.2
    dt = np.asarray(jax.nn.softplus(weights.draw_leaf(
        "dt_bias", key, (8192,), np.float32)), np.float64)
    assert 0.999e-3 < dt.min() < 1.01e-3 and 0.099 < dt.max() < 0.1001
    assert abs(np.log10(dt).mean() + 2.0) < 0.03      # log-uniform


def test_dense_leaves_are_drawn_by_their_formulas():
    """Each leaf of the dense tree is the formula of its branch: embedding
    0.02 z, norm scales and q/k/v biases 0.1 z, projections z / sqrt(fan
    in), z drawn per leaf (and per layer of a stacked leaf)."""
    import jax
    import jax.numpy as jnp
    arch = ARCHS["chatglm3-6b"].reduced()
    key = jax.random.PRNGKey(2 ** 31 + 3)
    names = set()
    for i, (path, leaf) in enumerate(_draw(arch)):
        name = weights._leaf_name(path)
        names.add(name)
        k = jax.random.fold_in(key, i)
        if weights.stacked(path):
            keys = jax.random.split(k, leaf.shape[0])
            z = jnp.stack([jax.random.normal(kk, leaf.shape[1:], jnp.float32)
                           for kk in keys])
        else:
            z = jax.random.normal(k, leaf.shape, jnp.float32)
        if name == "embed":
            want = z * 0.02
        elif name.endswith("_scale") or name in ("bq", "bk", "bv"):
            want = z * 0.1
        else:
            want = z / math.sqrt(leaf.shape[-2])
        np.testing.assert_array_equal(
            np.asarray(leaf).view(np.uint16),
            np.asarray(want.astype(leaf.dtype)).view(np.uint16), str(path))
    assert names == {"embed", "head", "final_ln_scale", "ln1_scale",
                     "ln2_scale", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
                     "w_gate", "w_up", "w_down"}
