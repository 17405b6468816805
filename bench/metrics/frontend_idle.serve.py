"""Share of the traced window in which no device operation runs while the
host is inside ``ServeEngine.generate`` (``unimem.generate``) but outside
its step loops (``unimem.prefill``, ``unimem.decode``): the device idle
that the serving front end's own work and the runtime's leave, in %."""

from bench.metrics import _program


def read(run):
    ps = _program.load(run)
    if ps is None or not ps.count("generate"):
        return None
    idle = _program.idle_ns(ps, "generate", ("prefill", "decode"))
    return 100.0 * idle / ps.window_ns
