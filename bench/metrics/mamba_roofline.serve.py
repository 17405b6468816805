"""The Mamba2 layers' share of their roofline in the decode step: their
least time (the larger of their FLOPs over peak and their least HBM bytes
over peak bandwidth, from the configuration's count module: every layer's
weights once, the float32 SSM state read and written, the convolution
window) over the device time of the operations in the program's
``unimem.mamba2`` scope."""

from bench.metrics import _scopes


def read(run):
    c = run.counts
    return _scopes.roofline_share(
        run, "mamba2", lambda arch, b, pos: c.mamba_flops(arch, b),
        lambda arch, b, pos: c.mamba_bytes(arch, b), "mamba_roofline.serve")
