"""Sharding rule tests — pure spec logic over an AbstractMesh (no devices),
plus the shard_map paths (pipeline schedule, embedding gather) on a
one-device mesh."""

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed import sharding as shd


MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def spec_eq(a, b):
    """PartitionSpec equality across JAX versions: newer JAX canonicalizes
    1-tuples (``('data',)``) to bare names (``'data'``); older versions
    compare entries strictly."""
    def canon(spec):
        return tuple(e[0] if isinstance(e, tuple) and len(e) == 1 else e
                     for e in spec)
    return canon(a) == canon(b)


def test_fit_drops_nondivisible_axes():
    # 8 heads cannot shard 16 ways -> dropped
    assert spec_eq(shd.fit(MESH, (8, 128), "model", None), P(None, None))
    assert spec_eq(shd.fit(MESH, (32, 128), "model", None), P("model", None))


def test_fit_keeps_divisible_prefix():
    # ("pod","data") over dim 4: pod(2) divides, pod*data(32) does not
    spec = shd.fit(MESH3, (4, 64), ("pod", "data"), None)
    assert spec_eq(spec, P("pod", None))


def test_param_specs_rules():
    pshapes = {
        "embed": jax.ShapeDtypeStruct((64000, 4096), jax.numpy.bfloat16),
        "head": jax.ShapeDtypeStruct((4096, 64000), jax.numpy.bfloat16),
        "blocks": {
            "attn": {"wq": jax.ShapeDtypeStruct((32, 4096, 4096),
                                                jax.numpy.bfloat16)},
            "mlp": {"w_down": jax.ShapeDtypeStruct((32, 11008, 4096),
                                                   jax.numpy.bfloat16)},
        },
    }
    specs = shd.param_specs(MESH, pshapes)
    assert spec_eq(specs["embed"], P(None, "model"))          # untied: d-sharded
    assert spec_eq(specs["head"], P(None, "model"))
    assert spec_eq(specs["blocks"]["attn"]["wq"], P(None, ("data",), "model"))
    assert spec_eq(specs["blocks"]["mlp"]["w_down"], P(None, "model", ("data",)))


def test_tied_embed_vocab_sharded():
    pshapes = {"embed": jax.ShapeDtypeStruct((256000, 2048),
                                             jax.numpy.bfloat16)}
    specs = shd.param_specs(MESH, pshapes, tied=True)
    assert spec_eq(specs["embed"], P("model", None))


def test_cache_specs_kv_head_fallback_to_sequence():
    cache = {"k": jax.ShapeDtypeStruct((28, 128, 32768, 2, 128),
                                       jax.numpy.bfloat16),
             "v": jax.ShapeDtypeStruct((28, 128, 32768, 2, 128),
                                       jax.numpy.bfloat16)}
    specs = shd.cache_specs(MESH, None, cache, batch=128)
    # kv=2 cannot split 16 ways -> sequence sharded over "model" (SP)
    assert spec_eq(specs["k"], P(None, ("data",), "model", None, None))


def test_cache_specs_kv_heads_when_divisible():
    cache = {"k": jax.ShapeDtypeStruct((32, 128, 32768, 32, 128),
                                       jax.numpy.bfloat16)}
    specs = shd.cache_specs(MESH, None, cache, batch=128)
    assert spec_eq(specs["k"], P(None, ("data",), None, "model", None))


def test_cache_specs_sp_when_batch_too_small():
    cache = {"k": jax.ShapeDtypeStruct((7, 1, 524288, 32, 64),
                                       jax.numpy.bfloat16)}
    specs = shd.cache_specs(MESH, None, cache, batch=1)
    # batch=1: shard the 500k sequence over "data" + heads over "model"
    assert spec_eq(specs["k"], P(None, None, "data", "model", None))


def test_opt_specs_mirror_params():
    pshapes = {"w": jax.ShapeDtypeStruct((4096, 4096), jax.numpy.bfloat16)}
    pspecs = shd.param_specs(MESH, pshapes)
    oshapes = {"mu": {"w": jax.ShapeDtypeStruct((4096, 4096),
                                                jax.numpy.float32)},
               "step": jax.ShapeDtypeStruct((), jax.numpy.int32)}
    ospecs = shd.opt_specs(MESH, oshapes, pshapes, pspecs)
    assert ospecs["mu"]["w"] == pspecs["w"]
    assert spec_eq(ospecs["step"], P())


def _one_device_mesh(names):
    import numpy as np
    return jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape((1,) * len(names)), names)


def test_pipeline_forward_runs_under_shard_map():
    """One stage, two microbatches: the shard_map schedule applies the
    layer to every microbatch."""
    import jax.numpy as jnp
    import numpy as np
    from repro.distributed.pipeline import pipeline_forward
    fn = pipeline_forward(lambda p, x: x * p["w"], 1, 2,
                          _one_device_mesh(("stage",)))
    xs = jnp.arange(24.0).reshape(2, 3, 4)
    out = fn({"w": jnp.full((1, 4), 2.0)}, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(xs) * 2.0)


@pytest.mark.parametrize("tied", [True, False])
def test_embed_lookup_shard_map_matches_take(tied):
    """With a mesh hint, the shard_map gather (and its custom backward)
    equals a plain take."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models.common import embed_lookup, set_mesh_hint
    table = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    tokens = jnp.array([[1, 5, 15], [0, 7, 3]], jnp.int32)
    set_mesh_hint(_one_device_mesh(("data", "model")))
    try:
        out = embed_lookup(table, tokens, tied=tied)
        grad = jax.grad(lambda t: embed_lookup(t, tokens, tied=tied).sum())(
            table)
    finally:
        set_mesh_hint(None)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.take(table, tokens, axis=0)))
    want = jax.grad(lambda t: jnp.take(t, tokens, axis=0).sum())(table)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want))
