"""Host time inside the runtime while serving, per ``generate`` call, in
ms: the union of the runtime's ``unimem.phase_enter``,
``unimem.phase_exit`` and ``unimem.iteration_exit`` spans over the
``unimem.generate`` spans in the traced window."""

from bench.metrics import _program


def read(run):
    ps = _program.load(run)
    if ps is None or not ps.count("generate"):
        return None
    ns = ps.union_ns("phase_enter", "phase_exit", "iteration_exit")
    return ns / 1e6 / ps.count("generate")
