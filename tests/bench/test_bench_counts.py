"""FLOP and byte counts against hand counts at tiny shapes, parameter
counts against the program's own tree, the count module a configuration
without a ``counts`` key gets, and the peaks table."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import counts, harness, peaks  # noqa: E402

DENSE = SimpleNamespace(n_layers=2, d_model=8, n_heads=4, n_kv_heads=2,
                        head_dim=None, d_ff=16, vocab_size=32,
                        attn_bias=True, tie_embeddings=False)


def test_dense_counts_by_hand():
    # head dim 2; q 8x8, k and v 8x4 each, o 8x8, mlp 3 x 8x16
    assert counts.dense_layer_matmul_params(DENSE) == 64 + 32 + 32 + 64 + 384
    # + bias 8 + 4 + 4 and two norm scales of 8
    assert counts.dense_layer_params(DENSE) == 576 + 16 + 16
    assert counts.dense_params(DENSE) == 32 * 8 + 2 * 608 + 8 + 8 * 32
    # batch 3 at position 4: 2 x (2 layers x 576 + head 256) per row, and
    # attention 2 layers x 4 x heads 4 x dim 2 x 5 keys per row
    assert counts.dense_decode_flops(DENSE, 3, 4) == 3 * (2 * 1408 + 320)
    # bytes: weights but the unread embedding rows, 3 rows looked up; KV of
    # 2 layers x 3 rows x (k, v) x 2 heads x dim 2 x 2 bytes x 5 positions
    w = (counts.dense_params(DENSE) - 256 + 3 * 8) * 2
    assert counts.dense_decode_bytes(DENSE, 3, 4) == w + 2 * 3 * 16 * 5


@pytest.mark.parametrize("pattern", ["attn"])
def test_parameter_counts_match_the_program_tree(pattern):
    import jax
    from repro.configs.base import ArchConfig
    from repro.models import lm
    arch = ArchConfig(name="t", family="dense", n_layers=2, d_model=16,
                      n_heads=4, n_kv_heads=2, d_ff=24, vocab_size=40,
                      attn_bias=True, rotary_fraction=0.5,
                      block_pattern=pattern)
    want = counts.dense_params(arch)
    tree = jax.eval_shape(lambda k: lm.init_params(arch, k),
                          jax.random.PRNGKey(0))
    assert sum(l.size for l in jax.tree_util.tree_leaves(tree)) == want


def test_a_configuration_naming_no_counts_gets_the_dense_counts():
    from repro.configs.base import ArchConfig
    cell = harness.find_cell("chatglm3-6b.serve")
    assert "counts" not in cell.config
    module = cell.counts()
    arch = ArchConfig(**cell.config["arch"])
    # the values the decode metrics divided by before count modules could
    # be named (batch 8, at the first, a typical and the last position)
    for pos, flops, nbytes in [(0, 95633801216.0, 11954786304.0),
                               (120, 96074203136.0, 11982311424.0),
                               (2047, 103146323968.0, 12424318976.0)]:
        assert module.decode_flops(arch, 8, pos) == flops
        assert module.decode_bytes(arch, 8, pos) == nbytes
        assert counts.dense_decode_flops(arch, 8, pos) == flops


def test_peaks_table_and_share_guard():
    v5e = peaks.peak_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak_for("cpu")
    assert peaks.share(50.0, 200.0, "x") == 25.0
    with pytest.raises(ValueError, match="reads 101"):
        peaks.share(101.0, 100.0, "x")
