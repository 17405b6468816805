"""Compile-only checks for a TPU v5e at real widths.

Each test lowers a Pallas kernel (or the gemma-2b decode step) for one chip
of a *described* ``v5e:2x2`` topology and compiles it with the TPU
compiler, without a chip attached: what the chip's compiler refuses
(an unlowerable primitive, a misaligned block, too much VMEM, a program
that does not fit HBM) fails here.  Nothing runs, so nothing here says
anything about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the test workers
all import this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.tiered_matmul import tiered_matmul
from repro.models import lm
from repro.serve.engine import build_decode_step

HBM_BYTES = 16 * 1024 ** 3          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except RuntimeError as e:
        # skip only where no TPU compiler is installed; any other failure
        # to describe the topology (a held library, an API change) fails
        if "TPU support not installed" not in str(e):
            raise
        pytest.skip(f"no TPU compiler installed: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles_at_gemma_2b_width(one_chip):
    B, K, G, D, T = 8, 1, 8, 256, 2048
    q = _spec((B, K, G, D), jnp.bfloat16, one_chip)
    kv = _spec((B, K, T, D), jnp.bfloat16, one_chip)
    length = _spec((), jnp.int32, one_chip)
    _assert_kernel(decode_attention.lower(q, kv, kv, length).compile())


def test_flash_attention_compiles_at_gemma_2b_width(one_chip):
    B, K, G, S, D = 1, 1, 8, 2048, 256
    q = _spec((B, K, G, S, D), jnp.bfloat16, one_chip)
    kv = _spec((B, K, S, D), jnp.bfloat16, one_chip)
    _assert_kernel(flash_attention.lower(q, kv, kv).compile())


def test_tiered_matmul_compiles_at_gemma_2b_mlp_width(one_chip):
    x = _spec((2048, 2048), jnp.bfloat16, one_chip)
    w = _spec((2048, 16384), jnp.bfloat16, one_chip)
    _assert_kernel(tiered_matmul.lower(x, w).compile())


def test_ssd_scan_compiles_at_zamba2_width(one_chip):
    B, H, S, N, P = 1, 32, 2048, 64, 64
    a = _spec((B, H, S), jnp.float32, one_chip)
    kq = _spec((B, H, S, N), jnp.float32, one_chip)
    v = _spec((B, H, S, P), jnp.float32, one_chip)
    _assert_kernel(ssd_scan.lower(a, kq, v, kq).compile())


def test_gemma_2b_decode_step_compiles_and_fits_one_chip(one_chip):
    """The serving step at full width (B=8, max_seq=2048), as ServeEngine
    jits it: it compiles for one v5e and its footprint fits in HBM."""
    cfg = get_config("gemma-2b")
    B, max_seq = 8, 2048
    place = lambda s: _spec(s.shape, s.dtype, one_chip)   # noqa: E731
    params = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda: lm.init_cache(cfg, B, max_seq)))
    token = _spec((B,), jnp.int32, one_chip)
    pos = _spec((), jnp.int32, one_chip)
    compiled = jax.jit(build_decode_step(cfg)).lower(
        params, cache, token, pos).compile()
    mem = compiled.memory_analysis()
    param_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree_util.tree_leaves(params))
    assert param_bytes > 4.9e9                  # really full width
    assert mem.argument_size_in_bytes >= param_bytes
    footprint = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert footprint < HBM_BYTES
