"""The ``zamba2-7b`` preset: its parameter count against the count by
hand from the published config, and a finite gradient through the Mamba2
chunked scan under the published draw of A and dt."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import weights  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import lm  # noqa: E402

# by hand (d 3584, d_in 7168, 112 heads of 64, d_state 64, 2 groups, conv
# 4, 32 heads of 224 over 7168, MLP 14336, adapter rank 128, vocab 32000)
MAMBA_LAYER = (3584 * (7168 + 7424 + 112) + 5 * 7424 + 3 * 112 + 7168
               + 7168 * 3584 + 3584)                            # 78.44 M
SHARED_BLOCK = (7168 + 3 * 7168 * 7168 + 7168 * 3584 + 3584
                + 3 * 3584 * 14336)                             # 333.98 M
APPLICATION = 3584 * 3584 + 128 * (3584 + 2 * 14336)            # 16.97 M
EMBED_AND_NORM = 32000 * 3584 + 3584


def test_zamba2_7b_parameter_count_matches_the_hand_count():
    cfg = get_config("zamba2-7b")
    assert (MAMBA_LAYER, SHARED_BLOCK, APPLICATION) == (
        78_437_456, 333_982_208, 16_973_824)
    whole = EMBED_AND_NORM + 81 * MAMBA_LAYER + 2 * SHARED_BLOCK \
        + 13 * APPLICATION
    cut = EMBED_AND_NORM + 42 * MAMBA_LAYER + 2 * SHARED_BLOCK \
        + 7 * APPLICATION
    assert cfg.n_params() == whole == 7_356_749_648
    assert dataclasses.replace(cfg, n_layers=42).n_params() == cut \
        == 4_195_845_920
    # and the tree the program builds holds that many
    shapes = jax.eval_shape(lambda: lm.init_params(cfg.reduced(),
                                                   jax.random.PRNGKey(0)))
    assert sum(int(np.prod(s.shape)) for s in
               jax.tree_util.tree_leaves(shapes)) == cfg.reduced().n_params()


def test_gradient_is_finite_over_a_full_chunk_under_the_published_draw():
    """256 tokens, one whole chunk of the scan, with A in [1, 16] and dt in
    [1e-3, 1e-1]: the decay summed over the chunk reaches e^-400, whose
    reciprocal above the diagonal overflows unless it is masked before the
    exponential."""
    cfg = get_config("zamba2-7b").reduced()
    params = weights.make_params(cfg, jax.random.PRNGKey(5))
    p32 = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), params)
    toks = jax.random.randint(jax.random.PRNGKey(6), (1, 256), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    grads = jax.jit(jax.grad(lambda p: lm.loss_fn(p, cfg, batch)[0]))(p32)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert np.isfinite(np.asarray(g)).all(), path
