"""Device time by the program's named scopes, and a part of the decode
step's share of its roofline.

The program wraps parts of its jitted step in ``jax.named_scope``\\ s named
``unimem.<name>`` (``repro.core.spans.scope``): the decode step of a
hybrid model wraps each Mamba2 layer in ``unimem.mamba2`` and each
shared-block application in ``unimem.shared_block``.  The compiled
instructions carry the scope in their ``op_name`` metadata.  A TPU trace's
operations (the ``XLA Ops`` line) are named by instruction alone, with no
metadata among their stats, but the profiler keeps each program's
optimized HLO in the ``/host:metadata`` plane (one event metadata per
program, named as on the ``XLA Modules`` line, with an ``Hlo Proto``
stat).  ``hlo_scopes`` reads the instructions' scopes from there with a
small protobuf reader (no protobuf schema is needed), ``load`` finds each
operation's program by the ``XLA Modules`` event it runs in and sums the
device time of each scope inside the ``bench.window`` span; control-flow
containers (``%while`` ...) are left out, since the operations they run
are listed themselves.  The reduction works on plain tuples, so it is
tested on synthetic traces.

A program without such scopes gives None, and so does every reader: the
metric is then left out of the line.
"""

from __future__ import annotations

import bisect
import functools
import os
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench import harness, peaks, xplane
from bench.metrics import _decode

WINDOW = "bench.window"
METADATA_PLANE = "/host:metadata"
SCOPE = re.compile(r"unimem\.(\w+)")
Op = Tuple[float, float, str, Optional[str]]

# protobuf field numbers: XSpace.planes; XPlane.name, .event_metadata (a
# map: key 1, value 2); XEventMetadata.name, .stats; XStat.bytes_value;
# HloProto.hlo_module; HloModuleProto.computations;
# HloComputationProto.instructions; HloInstructionProto.name, .metadata;
# OpMetadata.op_name
PLANES, PLANE_NAME, EVENT_METADATA, MAP_VALUE = 1, 2, 4, 2
EVENT_NAME, EVENT_STATS, STAT_BYTES = 2, 5, 6
HLO_MODULE, COMPUTATIONS, INSTRUCTIONS = 1, 3, 2
INSTR_NAME, INSTR_METADATA, OP_NAME = 1, 7, 2


# ------------------------------------------------------------- protobuf
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one serialized protobuf message: varints
    as ints, length-delimited fields as memoryviews, fixed-width ones as
    their bytes."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _instruction_scopes(hlo_proto) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for f, module in fields(hlo_proto):
        if f != HLO_MODULE:
            continue
        for f, comp in fields(module):
            if f != COMPUTATIONS:
                continue
            for f, instr in fields(comp):
                if f != INSTRUCTIONS:
                    continue
                name = op_name = ""
                for g, v in fields(instr):
                    if g == INSTR_NAME:
                        name = _text(v)
                    elif g == INSTR_METADATA:
                        op_name = next((_text(w) for h, w in fields(v)
                                        if h == OP_NAME), "")
                m = SCOPE.search(op_name)
                if m:
                    out[name] = m.group(1)
    return out


def hlo_scopes(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """Per program (by its name in the trace), the scope of each
    instruction whose ``op_name`` carries one."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in fields(xspace):
        if f != PLANES:
            continue
        entries = []
        for g, v in fields(plane):
            if g == PLANE_NAME and _text(v) != METADATA_PLANE:
                break
            if g == EVENT_METADATA:
                entries.append(v)
        for entry in entries:
            for g, md in fields(entry):
                if g != MAP_VALUE:
                    continue
                name, protos = "", []
                for h, v in fields(md):
                    if h == EVENT_NAME:
                        name = _text(v)
                    elif h == EVENT_STATS:
                        protos += [w for k, w in fields(v) if k == STAT_BYTES]
                for proto in protos:
                    scopes = _instruction_scopes(proto)
                    if scopes:
                        out.setdefault(name, {}).update(scopes)
    return out


# ------------------------------------------------------------ reduction
def _program(scopes: Dict[str, Dict[str, str]], name: str
             ) -> Dict[str, str]:
    """The scopes of the program named ``name`` on the ``XLA Modules``
    line: by its full name, else by the name before its id; none for a
    program that carries no scope (``hlo_scopes`` lists no such program),
    whose instruction names may well repeat the scoped program's."""
    if name in scopes:
        return scopes[name]
    merged: Dict[str, str] = {}
    for k, v in scopes.items():
        if k.split("(")[0] == name.split("(")[0]:
            merged.update(v)
    return merged


def scoped_ops(modules: Sequence[xplane.Interval],
               ops: Sequence[xplane.Interval],
               scopes: Dict[str, Dict[str, str]]) -> List[Op]:
    """Each operation with its scope: the instruction's scope in the
    program whose ``XLA Modules`` event the operation starts in."""
    mods = sorted(modules)
    starts = [s for s, _, _ in mods]
    by_name = {n: _program(scopes, n) for n in {m[2] for m in mods}}
    out: List[Op] = []
    for s, e, name in ops:
        k = bisect.bisect_right(starts, s) - 1
        prog = by_name[mods[k][2]] if k >= 0 and s < mods[k][1] else {}
        out.append((s, e, name, prog.get(name.lstrip("%"))))
    return out


def reduce(windows: Sequence[Tuple[float, float]],
           ops: Sequence[Op]) -> Optional[Dict[str, float]]:
    """Device seconds per scope inside the windows' span; None when no
    operation in it carries a scope."""
    if not windows:
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    out: Dict[str, float] = {}
    for s, e, name, scope in ops:
        if scope is None or name.startswith(xplane.CONTAINERS):
            continue
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out[scope] = out.get(scope, 0.0) + (e - s) / 1e9
    return out or None


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> Optional[Dict[str, float]]:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    scopes = hlo_scopes(raw)
    if not scopes:
        return None
    pd = ProfileData.from_serialized_xspace(raw)
    windows: List[Tuple[float, float]] = []
    ops: List[Op] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: [(ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name.split(" = ")[0])
                                 for ev in line.events]
                     for line in plane.lines
                     if line.name in (xplane.OPS_LINE, xplane.MODULES_LINE)}
            ops += scoped_ops(lines.get(xplane.MODULES_LINE, []),
                              lines.get(xplane.OPS_LINE, []), scopes)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                windows += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events if ev.name == WINDOW]
    return reduce(windows, ops)


def load(run) -> Optional[Dict[str, float]]:
    """Device seconds per scope in ``run``'s traced window; None for an
    untraced run or a trace without scopes."""
    if run.trace is None:
        return None
    try:
        path = xplane.newest_xplane(str(harness.TRACE_DIR))
    except FileNotFoundError:
        return None
    return _load(path, os.path.getmtime(path))


def roofline_share(run, scope: str, flops, nbytes, what: str):
    """Least time of the scope's work over its device time: per decode
    step, the larger of ``flops(arch, batch, pos)`` over peak FLOP/s and
    ``nbytes(arch, batch, pos)`` over peak bandwidth (the configuration's
    count module), averaged over the traced calls' steps and multiplied by
    the step programs the trace ran."""
    secs = (load(run) or {}).get(scope)
    if not secs:
        return None
    _, n = _decode.step_device(run)
    if not n:
        return None
    B, pk = run.counters["batch"], run.peak
    least = [max(flops(run.arch, B, pos) / pk["bf16_flops"],
                 nbytes(run.arch, B, pos) / pk["hbm_bytes_per_s"])
             for P, m in run.extra["traced_calls"] for pos in range(P + m)]
    if not least:
        return None
    return peaks.share(sum(least) / len(least) * n, secs, what)
