"""Host time blocked on copies, per sweep, in ms: the union of the
runtime's ``unimem.fence_wait`` spans (a phase's fence on the copies it
reads) and ``unimem.chain_wait`` spans (a fetch held behind the eviction
freeing its space) in the traced window."""

from bench.metrics import _program


def read(run):
    ps = _program.load(run)
    if ps is None:
        return None
    ns = ps.union_ns("fence_wait", "chain_wait")
    return ns / 1e6 / run.counters["iterations"]
