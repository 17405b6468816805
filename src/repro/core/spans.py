"""Host spans on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``unimem.<name>``: while a profiler session is active it lands on the
host plane of the trace, on the same clock as the device's operations, so
an idle gap on the device can be put down to what the runtime or the
serving front end was doing.  With no session active it records nothing
and costs the Python enter and exit alone, so there is no switch.

Names are static; what varies (an object, a byte count, a phase) goes in
as keyword arguments, which the trace keeps as the event's stats.

``scope(name)`` is the device-side counterpart: a ``jax.named_scope``
named ``unimem.<name>`` around model code being traced for jit.  It costs
nothing at run time; the compiled operations carry the name in their
``op_name`` metadata, which the device trace shows for each operation.
"""

from __future__ import annotations

import jax
from jax.profiler import TraceAnnotation

PREFIX = "unimem."


def span(name: str, **args) -> TraceAnnotation:
    return TraceAnnotation(PREFIX + name, **args)


def scope(name: str):
    return jax.named_scope(PREFIX + name)
