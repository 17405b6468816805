"""Device time by the program's named scopes, and the two per-layer
metrics of the hybrid decode step that read it: the scopes read from the
optimized HLO a real (CPU) trace keeps, the reduction on synthetic
operations."""

import dataclasses
import glob
import os
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402
from bench.metrics import _scopes  # noqa: E402

BENCH = Path(__file__).resolve().parents[2] / "bench"


def _trace(tmp_path, scoped: bool) -> bytes:
    import jax
    import jax.numpy as jnp
    from repro.core.spans import scope

    @jax.jit
    def step(x):
        with (scope("mamba2") if scoped else jax.named_scope("other")):
            x = jnp.sin(x) @ x
        with (scope("shared_block") if scoped else jax.named_scope("else")):
            return jnp.cos(x) @ x

    x = jnp.ones((64, 64))
    jax.block_until_ready(step(x))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        jax.block_until_ready(step(x))
    jax.profiler.stop_trace()
    path = max(glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                              / "*.xplane.pb")), key=os.path.getmtime)
    return Path(path).read_bytes()


def test_scopes_are_read_from_the_hlo_the_trace_keeps(tmp_path):
    got = _scopes.hlo_scopes(_trace(tmp_path, scoped=True))
    (prog, scopes), = [(k, v) for k, v in got.items()
                       if k.startswith("jit_step")]
    dots = sorted(s for name, s in scopes.items()
                  if name.startswith("dot"))
    assert dots == ["mamba2", "shared_block"]
    assert _scopes.hlo_scopes(_trace(tmp_path / "b", scoped=False)) == {}


def test_protobuf_fields_are_decoded():
    # field 1 varint 150, field 2 bytes "hi", field 5 fixed32
    msg = bytes([0x08, 0x96, 0x01, 0x12, 0x02]) + b"hi" + bytes([0x2D]) \
        + b"\x00\x00\x80\x3f"
    got = [(f, bytes(v) if isinstance(v, memoryview) else v)
           for f, v in _scopes.fields(msg)]
    assert got == [(1, 150), (2, b"hi"), (5, b"\x00\x00\x80\x3f")]


def test_operations_take_the_scope_of_the_program_they_run_in():
    """As ``hlo_scopes`` builds its input: a program with no scoped
    instruction has no entry, and its operations, whose names repeat the
    scoped program's, take no scope."""
    modules = [(0, 100, "jit_decode_step(7)"), (100, 120, "jit_other(9)")]
    ops = [(10, 20, "%fusion.1"), (30, 40, "%fusion.2"),
           (105, 110, "%fusion.1"), (130, 140, "%fusion.1")]
    got = _scopes.scoped_ops(modules, ops, {
        "jit_decode_step(7)": {"fusion.1": "mamba2"}})
    assert [op[3] for op in got] == ["mamba2", None, None, None]
    # a program the metadata names with another id: found by its name
    got = _scopes.scoped_ops(modules, ops, {
        "jit_decode_step(123)": {"fusion.1": "mamba2"}})
    assert [op[3] for op in got] == ["mamba2", None, None, None]


def test_reduction_sums_each_scope_inside_the_window_without_containers():
    ops = [(0, 50, "%fusion.1", "mamba2"),          # half inside
           (100, 130, "%fusion.2", "mamba2"),
           (130, 200, "%fusion.3", "shared_block"),
           (90, 400, "%while.4", "mamba2"),         # a container
           (140, 150, "%copy.5", None),             # no scope
           (500, 600, "%fusion.6", "mamba2")]       # after the window
    got = _scopes.reduce([(25, 120), (110, 300)], ops)
    assert got == pytest.approx({"mamba2": 55e-9, "shared_block": 70e-9})
    assert _scopes.reduce([(25, 300)], [(0, 9, "%f", None)]) is None
    assert _scopes.reduce([], ops) is None


@dataclasses.dataclass
class FakeRun:
    counts: object
    counters: dict
    extra: dict
    trace: object = True
    arch: object = None
    peak: dict = dataclasses.field(default_factory=lambda: {
        "bf16_flops": 100.0, "hbm_bytes_per_s": 10.0})


def test_roofline_share_is_least_time_over_scope_time(monkeypatch):
    from bench.metrics import _decode
    monkeypatch.setattr(_scopes, "load", lambda run: {"mamba2": 18.0})
    monkeypatch.setattr(_decode, "step_device", lambda run: (30.0, 5))
    run = FakeRun(counts=None, counters={"batch": 2},
                  extra={"traced_calls": [(2, 1), (1, 1)]})
    # steps at positions 0, 1, 2 and 0, 1; 10 (pos + 1) bytes at 10 B/s
    # bound each by bandwidth: a mean least of 1.8 s, times 5 programs
    share = _scopes.roofline_share(
        run, "mamba2", lambda a, b, pos: 1.0,
        lambda a, b, pos: 10.0 * (pos + 1), "x")
    assert share == pytest.approx(100.0 * 1.8 * 5 / 18.0)
    assert _scopes.roofline_share(run, "shared_block", None, None, "x") \
        is None
    monkeypatch.setattr(_scopes, "load", lambda run: None)
    assert _scopes.roofline_share(run, "mamba2", None, None, "x") is None


def test_readers_give_nothing_for_a_trace_without_scopes(tmp_path,
                                                         monkeypatch):
    """A program that opens no scope (as before they existed) gives None,
    so the metrics are left out of its line."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    _trace(tmp_path, scoped=False)
    run = FakeRun(counts=None, counters={"batch": 1},
                  extra={"traced_calls": [(1, 1)]})
    assert _scopes.load(run) is None
    for name in ("mamba_roofline.serve", "shared_block_roofline.serve"):
        read = harness.load_module(BENCH / "metrics" / f"{name}.py").read
        assert read(run) is None
