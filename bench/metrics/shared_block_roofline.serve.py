"""The shared-block applications' share of their roofline in the decode
step: their least time (the larger of their FLOPs over peak and their
least HBM bytes over peak bandwidth, from the configuration's count
module: each application's block weights, ``linear`` and adapter, the KV
cache read up to the position and one new entry) over the device time of
the operations in the program's ``unimem.shared_block`` scope."""

from bench.metrics import _scopes


def read(run):
    c = run.counts
    return _scopes.roofline_share(
        run, "shared_block",
        lambda arch, b, pos: c.shared_block_flops(arch, b, pos),
        lambda arch, b, pos: c.shared_block_bytes(arch, b, pos),
        "shared_block_roofline.serve")
