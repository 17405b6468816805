"""Operation and byte counts the benchmark divides by measured time.

Every count is the least work the algorithm needs, computed from the
configuration's sizes alone (never from the program's own cost analysis):
a share of a peak built on these counts cannot exceed 100% unless the
program skipped work.

One module per kind of model, found by the configuration's ``counts`` key
(``bench/counts/<name>.py``; ``dense`` where the key is absent), as its
reference is found by ``reference``.  Each gives ``decode_flops(arch,
batch, pos)`` and ``decode_bytes(arch, batch, pos)`` of one decode step.
"""

from bench.counts.dense import (BF16, dense_decode_bytes,  # noqa: F401
                                dense_decode_flops, dense_layer_matmul_params,
                                dense_layer_params, dense_params, head_dim)
