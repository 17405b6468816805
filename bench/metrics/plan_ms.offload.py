"""Host time in the runtime's profiling and planning, per sweep, in ms:
the union of its ``unimem.phase_exit`` spans (instrumentation collected,
the profiler and the variation monitor fed, a drift response) and
``unimem.iteration_exit`` spans (plan builds, calibration, audits; every
``unimem.plan`` lies inside one of the two) in the traced window."""

from bench.metrics import _program


def read(run):
    ps = _program.load(run)
    if ps is None:
        return None
    ns = ps.union_ns("phase_exit", "iteration_exit")
    return ns / 1e6 / run.counters["iterations"]
