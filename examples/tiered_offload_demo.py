"""Real tier moves: the Unimem mover relocating actual JAX arrays between
memory kinds (``device`` <-> ``pinned_host``) with async device_put — the
production HBM/host path.  The CPU backend exposes the same memory kinds;
a backend without ``pinned_host`` fails instead of faking the moves.

v2 session API: arrays are registered pytree-natively (leaf byte spans
recorded), the loop is the ``iteration()``/``phase()`` context managers,
and the copy engine comes from the string-keyed backend registry —
``backend="jax_async"`` selects asynchronous device_put with per-leaf
fencing (tier flips when a copy *lands*, settled without blocking at phase
boundaries).

  PYTHONPATH=src python examples/tiered_offload_demo.py
"""

import sys
import time
sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.core import PAPER_DRAM_NVM, RuntimeConfig, UnimemRuntime

MB = 1024 ** 2


def main() -> None:
    dev = jax.devices()[0]
    kinds = [m.kind for m in dev.addressable_memories()]
    print("device:", dev, "memories:", kinds)
    machine = PAPER_DRAM_NVM
    host_kind = machine.slow.memory_kind
    rt = UnimemRuntime(machine,
                       RuntimeConfig(fast_capacity_bytes=64 * MB,
                                     enable_partitioning=False,
                                     backend="jax_async"))

    # register real arrays as target data objects (all start on host tier)
    sharding = jax.sharding.SingleDeviceSharding(
        dev, memory_kind=host_kind)
    objs = {}
    for name, mbs in (("weights_hot", 24), ("kv_block", 24),
                      ("opt_state_cold", 48)):
        arr = jax.device_put(
            jnp.ones((mbs * MB // 4,), jnp.float32), sharding)
        objs[name] = rt.register(name, arr)

    # iteration 1 profiles; accesses favor the hot objects
    for it in range(4):
        with rt.iteration():
            with rt.phase("compute", elapsed=0.05,
                          accesses={"weights_hot": 4e5, "kv_block": 3e5}):
                time.sleep(0.01)
            with rt.phase("update", elapsed=0.02,
                          accesses={"opt_state_cold": 5e4}):
                pass
        for name, obj in objs.items():
            kind = (jax.tree_util.tree_leaves(obj.payload)[0]
                    .sharding.memory_kind)
            print(f"  iter {it}: {name:16s} tier={obj.tier:5s} "
                  f"memory_kind={kind}")
    print("stats:", rt.stats())


if __name__ == "__main__":
    main()
