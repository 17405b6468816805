"""Substrate tests: data pipeline, checkpointing, optimizer, compression,
partitioning, mover."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PAPER_DRAM_NVM
from repro.core.data_objects import ObjectRegistry
from repro.core.mover import SimTierBackend
from repro.core.partition import (auto_partition, partition_object,
                                  partition_object_spans)
from repro.core.phase import build_phase_graph
from repro.data import DataConfig, SyntheticTokenPipeline

MB = 1024 ** 2


# ----------------------------------------------------------------- data
def test_pipeline_deterministic_and_seekable():
    cfg = DataConfig(vocab_size=512, seq_len=64, global_batch=4)
    p1 = SyntheticTokenPipeline(cfg)
    p2 = SyntheticTokenPipeline(cfg)
    b1 = p1.batch_at(7)
    b2 = p2.batch_at(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = p1.batch_at(8)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    assert int(b1["tokens"].max()) < 512


# ----------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_and_gc(tmp_path):
    from repro.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
             "opt": {"step": jnp.int32(3)}}
    for s in (10, 20, 30):
        mgr.save(s, state, blocking=True)
    assert mgr.list_steps() == [20, 30]      # GC keeps last 2
    step, restored = mgr.restore()
    assert step == 30
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.arange(6.0).reshape(2, 3))
    assert int(restored["opt"]["step"]) == 3


def test_checkpoint_atomic_no_partial(tmp_path):
    from repro.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"x": jnp.ones((4,))}, blocking=True)
    # a stale tmp dir must never be listed
    os.makedirs(tmp_path / "step_99.tmp", exist_ok=True)
    assert 99 not in mgr.list_steps()


# -------------------------------------------------------------- optimizer
@pytest.mark.parametrize("moments", ["float32", "bfloat16", "int8"])
def test_adamw_converges_quadratic(moments):
    from repro.optim import AdamWConfig, adamw_update, init_opt_state
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, moments_dtype=moments)
    params = {"w": jnp.array([5.0, -3.0])}
    state = init_opt_state(params, cfg)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(150):
        grads = jax.grad(loss)(params)
        params, state, _ = adamw_update(grads, params, state, cfg,
                                        jnp.float32(0.1))
    assert float(loss(params)) < 1e-2


def test_grad_clip_applied():
    from repro.optim import AdamWConfig, adamw_update, init_opt_state
    cfg = AdamWConfig(lr=1e-3, grad_clip=1.0)
    params = {"w": jnp.zeros((4,))}
    state = init_opt_state(params, cfg)
    huge = {"w": jnp.full((4,), 1e6)}
    _, _, metrics = adamw_update(huge, params, state, cfg, jnp.float32(1e-3))
    assert float(metrics["grad_norm"]) > 1.0   # reported pre-clip


# ------------------------------------------------------------ compression
def test_int8_error_feedback_unbiased():
    from repro.distributed.grad_compression import (dequantize_int8,
                                                    quantize_int8)
    x = jax.random.normal(jax.random.PRNGKey(0), (1024,)) * 3.0
    q, s = quantize_int8(x)
    x2 = dequantize_int8(q, s, x.shape)
    # block-wise int8 keeps ~1% relative error on normal data
    assert float(jnp.abs(x - x2).max()) < 0.05
    # error feedback: residual + sent == original
    resid = x - x2
    np.testing.assert_allclose(np.asarray(x2 + resid), np.asarray(x),
                               rtol=1e-6)


# ----------------------------------------------------------- partitioning
def test_partition_object_splits_sizes_and_payload():
    reg = ObjectRegistry()
    arr = jnp.arange(1000, dtype=jnp.float32)
    reg.alloc("big", 4000, chunkable=True, payload=arr)
    chunks = partition_object(reg, "big", 1024)
    assert "big" not in reg
    assert sum(c.size_bytes for c in chunks) == 4000
    total = jnp.concatenate([c.payload for c in chunks])
    np.testing.assert_array_equal(np.asarray(total), np.arange(1000))


def _pytree_object(reg):
    """A 3-leaf pytree object with its leaf spans, as register() makes it."""
    tree = {"a": jnp.ones((4, 64), jnp.float32),        # 1024 B
            "b": jnp.full((2, 64), 2.0, jnp.float32),   # 512 B
            "c": jnp.full((4, 64), 3.0, jnp.float32)}   # 1024 B
    obj = reg.alloc("tree", 2560, chunkable=True, payload=tree)
    obj.leaf_spans = [("['a']", 0, 1024), ("['b']", 1024, 512),
                      ("['c']", 1536, 1024)]
    return tree


def test_partition_pytree_at_leaf_edges_carries_whole_leaves():
    reg = ObjectRegistry()
    tree = _pytree_object(reg)
    chunks = partition_object_spans(reg, "tree", [1024, 2560])
    assert [c.size_bytes for c in chunks] == [1024, 1536]
    assert [id(x) for x in chunks[0].payload] == [id(tree["a"])]
    assert [id(x) for x in chunks[1].payload] == [id(tree["b"]),
                                                  id(tree["c"])]


def test_partition_never_makes_payload_less_chunks():
    """A cut inside a leaf cannot carry the payload: the object stays
    whole rather than becoming logical (payload-free) chunks."""
    reg = ObjectRegistry()
    _pytree_object(reg)
    assert partition_object(reg, "tree", 1000) == [reg["tree"]]
    assert reg["tree"].payload is not None and reg["tree"].parent is None
    reg.alloc("odd", 300, chunkable=True,
              payload=jnp.ones((2, 150), jnp.uint8))     # 2-D, no spans
    assert partition_object(reg, "odd", 100) == [reg["odd"]]


def test_auto_partition_only_chunkable_oversize():
    reg = ObjectRegistry()
    reg.alloc("big_chunkable", 100 * MB, chunkable=True)
    reg.alloc("big_rigid", 100 * MB, chunkable=False)
    reg.alloc("small", 1 * MB, chunkable=True)
    graph = build_phase_graph([("p0", {"big_chunkable": 1e6,
                                       "big_rigid": 1e6, "small": 1e6})],
                              times=[0.1])
    done = auto_partition(reg, graph, 10 * MB)
    assert done == ["big_chunkable"]
    assert "big_rigid" in reg and "small" in reg
    # refs rewritten to chunks
    assert not graph[0].references("big_chunkable")
    assert any(o.startswith("big_chunkable#") for o in graph[0].refs)


# ----------------------------------------------------------------- mover
def test_sim_mover_overlap_semantics():
    clock = {"t": 0.0}
    backend = SimTierBackend(PAPER_DRAM_NVM, lambda: clock["t"])
    reg = ObjectRegistry()
    obj = reg.alloc("a", int(PAPER_DRAM_NVM.copy_bw))  # 1 second copy
    h = backend.start_move(obj, "fast")
    assert obj.tier == "fast"
    clock["t"] = 0.5
    assert backend.wait(h) == pytest.approx(0.5)   # half the copy remains
    clock["t"] = 2.0
    assert backend.wait(h) == 0.0                  # fully overlapped


# ---------------------------------------------------------- compile cache
def test_compile_cache_dir_from_env_or_fixed_repo_path(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to <repo>/.jax_cache, a fixed path that git ignores."""
    from pathlib import Path
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = Path(__file__).resolve().parents[1]
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (repo / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
