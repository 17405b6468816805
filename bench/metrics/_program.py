"""What the metrics that read the program's own spans share.

The runtime and the serving front end open ``unimem.*`` spans
(``repro.core.spans``) on the profiler's clock.  ``load`` reads them from
the newest ``*.xplane.pb`` under ``harness.TRACE_DIR``, the directory a
``--trace 1`` run traces into, together with the benchmark's
``bench.window`` span and the device's operations.  The trace is read once
per file, after the window, and the reduction works on plain
``(start_ns, end_ns, name)`` tuples, so it is tested on synthetic spans.

A program that opens no ``unimem.*`` span gives ``None``, and so does
every reader: the metric is then left out of the line.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Dict, List, Optional, Sequence, Tuple

from bench import harness, xplane

PREFIX = "unimem."
WINDOW = "bench.window"
Span = Tuple[float, float]


@dataclasses.dataclass
class ProgramSpans:
    """The program's spans, clipped to the traced window, by name without
    the prefix; and the union of device operations over the window."""

    lo: float
    hi: float
    spans: Dict[str, List[Span]]
    busy: List[Span]

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo

    def union(self, *names: str) -> List[Span]:
        return xplane.union([(s, e, "") for n in names
                             for s, e in self.spans.get(n, ())])

    def union_ns(self, *names: str) -> float:
        return sum(e - s for s, e in self.union(*names))

    def count(self, name: str) -> int:
        return len(self.spans.get(name, ()))


def reduce(host: Sequence[xplane.Interval],
           ops: Sequence[xplane.Interval]) -> Optional[ProgramSpans]:
    """``host``: the ``bench.window`` span and the ``unimem.*`` spans;
    ``ops``: device operations.  None when no program span lies in the
    window."""
    wins = [(s, e) for s, e, n in host if n == WINDOW]
    if not wins:
        return None
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    spans: Dict[str, List[Span]] = {}
    for s, e, n in xplane.clip([h for h in host if h[2].startswith(PREFIX)],
                               lo, hi):
        spans.setdefault(n[len(PREFIX):], []).append((s, e))
    if not spans:
        return None
    return ProgramSpans(lo, hi, spans, xplane.union(xplane.clip(ops, lo, hi)))


def subtract(a: Sequence[Span], b: Sequence[Span]) -> List[Span]:
    """The parts of the sorted, disjoint spans ``a`` that no span of the
    sorted, disjoint ``b`` covers (one pass over both)."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def idle_ns(ps: ProgramSpans, inside: str, outside: Sequence[str]) -> float:
    """Window time with no device operation while the host is inside span
    ``inside`` and outside every span named in ``outside``."""
    host = subtract(ps.union(inside), ps.union(*outside))
    return sum(e - s for s, e in subtract(host, ps.busy))


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> Optional[ProgramSpans]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host: List[xplane.Interval] = []
    ops: List[xplane.Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == xplane.OPS_LINE:
                    ops += [(ev.start_ns, ev.start_ns + ev.duration_ns, "")
                            for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW or ev.name.startswith(PREFIX):
                        host.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns, ev.name))
    return reduce(host, ops)


def load(run) -> Optional[ProgramSpans]:
    """The program's spans in ``run``'s traced window; None for an
    untraced run or a trace without them."""
    if run.trace is None:
        return None
    try:
        path = xplane.newest_xplane(str(harness.TRACE_DIR))
    except FileNotFoundError:
        return None
    return _load(path, os.path.getmtime(path))
