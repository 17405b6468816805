"""Cells, configurations, traffic mixes, runners and metrics are found by
name, BENCHMARK.json keeps to its schema, and a cell can be added from new
files alone."""

import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = harness.find_cell(cell)
    assert c.config["name"] == c.spec["config"]
    assert hasattr(c.runner(), "run")
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(c.reader(m["name"]))


def test_benchmark_json_schema():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= BM["run_seconds"] <= 51
    for p in BM["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/")
    metrics = BM["end_to_end"] + BM["per_layer"]
    names = [x["name"] for x in BM["configs"] + BM["workloads"] + metrics]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BM["end_to_end"]}
    for m in BM["per_layer"]:
        assert m["moves"] in e2e and m["layer"]


def test_a_cell_added_from_new_files_alone(tmp_path):
    """A later change adds a configuration, a mix and a metric as files
    and entries; nothing that exists is edited."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "runners"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(ROOT / "bench" / "runners" / "weight_sweep.py",
                bench / "runners")
    (bench / "configs" / "tiny-dense.json").write_text(json.dumps(
        {"name": "tiny-dense", "arch": {"name": "tiny-dense"}}))
    (bench / "traffic" / "offload-lru.json").write_text(json.dumps(
        {"runner": "weight_sweep", "policy": "lru"}))
    (bench / "metrics" / "sweeps.py").write_text(
        "def read(run):\n    return run.counters['iterations']\n")
    bm = {**BM,
          "configs": BM["configs"] + [{"name": "tiny-dense",
                                       "file": "bench/configs/tiny-dense.json"}],
          "workloads": BM["workloads"] + [{"name": "tiny-dense.offload-lru",
                                           "config": "tiny-dense",
                                           "traffic": "offload-lru",
                                           "chips": 1}],
          "per_layer": BM["per_layer"] + [{"name": "sweeps",
                                           "workloads": ["tiny-dense.offload-lru"]}]}
    c = harness.find_cell("tiny-dense.offload-lru", bm, bench_dir=bench)
    assert c.traffic["policy"] == "lru"
    assert c.runner().decode_phases
    assert [m["name"] for m in c.per_layer] == ["sweeps"]
    run = type("Run", (), {"counters": {"iterations": 7}})()
    assert c.reader("sweeps")(run) == 7
    with pytest.raises(KeyError, match="no workload"):
        harness.find_cell("nope", bm, bench_dir=bench)


def test_seed_key_keeps_all_64_bits():
    import jax
    a = jax.random.key_data(harness.seed_key(5))
    b = jax.random.key_data(harness.seed_key(2 ** 40 + 5))
    assert a.tolist() != b.tolist()
    assert jax.random.key_data(harness.seed_key(2 ** 31 + 1)).tolist() == \
        [0, 2 ** 31 + 1]


HYBRID_REFERENCE = '''"""Stand-in reference for a plumbing test: the program's own forward pass
in float32 at ``highest``.  It checks that a cell is wired end to end, not
the model, so it may share the program's code.  Sequences are padded at
the end to a multiple of 256 (causal: the rows read are unchanged)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from repro.models import lm


@functools.partial(jax.jit, static_argnames="arch")
def _forward(params, arch, tokens):
    p32 = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        return lm.forward(p32, arch, tokens[None])[0][0]


def logits(params, arch, tokens, rows, quant="none"):
    padded = np.zeros(-(-len(tokens) // 256) * 256, np.int32)
    padded[:len(tokens)] = tokens
    out = _forward(params, arch, jnp.asarray(padded))
    return np.asarray(out)[np.asarray(rows)]
'''

HYBRID_COUNTS = '''CALLS = []


def decode_flops(arch, batch, pos):
    CALLS.append(("flops", arch.block_pattern, batch, pos))
    return 1000.0 * batch + pos


def decode_bytes(arch, batch, pos):
    CALLS.append(("bytes", arch.block_pattern, batch, pos))
    return 2000.0 * batch + pos
'''

HYBRID_ARCH = dict(name="tiny-hybrid", family="hybrid", n_layers=4,
                   d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
                   d_ff=128, vocab_size=128, ssm_state=16, ssm_head_dim=16,
                   attn_every=2, block_pattern="mamba_shared_attn",
                   tie_embeddings=True, max_position=1024)
# At this size the program's widest gap read 0-0.0067 over eight seeds
# (CPU); the limit is the dense serving test's 0.1.
HYBRID_TRAFFIC = dict(runner="closed_loop_serve", batch=2, max_seq=64,
                      pool=4, sample_sequences=4,
                      prompt=dict(median=8, sigma=0.8, min=4, max=24),
                      new_tokens=dict(median=12, sigma=0.5, min=8, max=24),
                      gap_limit=0.1, trace_seconds=8)


def test_a_serving_cell_of_another_block_pattern_added_from_new_files_alone(
        tmp_path):
    """A hybrid (Mamba2 + shared attention) serving cell comes from a
    configuration naming its own reference and count modules, a traffic
    file and list entries: the benchmark's files are copied unchanged."""
    import time
    import jax
    from bench.metrics import _decode
    from bench.run import Run, execute
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("_trace", "__pycache__"))
    (bench / "configs" / "tiny-hybrid.json").write_text(json.dumps(
        {"name": "tiny-hybrid", "arch": HYBRID_ARCH,
         "reference": "tiny_hybrid_forward", "counts": "tiny_hybrid"}))
    (bench / "reference" / "tiny_hybrid_forward.py").write_text(
        HYBRID_REFERENCE)
    (bench / "counts" / "tiny_hybrid.py").write_text(HYBRID_COUNTS)
    (bench / "traffic" / "serve-tiny.json").write_text(
        json.dumps(HYBRID_TRAFFIC))
    cell = "tiny-hybrid.serve-tiny"

    def listing(metrics, names):
        return [{**m, "workloads": m["workloads"] + [cell]}
                if m["name"] in names else m for m in metrics]

    bm = {**BM,
          "configs": BM["configs"] + [{"name": "tiny-hybrid",
                                       "file": "bench/configs/tiny-hybrid.json"}],
          "workloads": BM["workloads"] + [{"name": cell,
                                           "config": "tiny-hybrid",
                                           "traffic": "serve-tiny",
                                           "chips": 1}],
          "end_to_end": listing(BM["end_to_end"], {"serve_tok_s"}),
          "per_layer": listing(BM["per_layer"], {"decode_roofline.serve",
                                                 "decode_mfu.serve"})}
    c = harness.find_cell(cell, bm, bench_dir=bench)
    assert [m["name"] for m in c.per_layer] == ["decode_roofline.serve",
                                                "decode_mfu.serve"]
    ctx = harness.Context(cell=c, seed=2 ** 31 + 11, seconds=0.5,
                          trace=False, t_start=time.perf_counter())
    line = execute(ctx, jax.devices()[0])
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"setup_s", "serve_tok_s"}
    assert line["_extra"]["window_compiles"] == 0
    # the decode readers' steps come from the configuration's count module
    counted = c.counts()
    assert counted is not harness.load_module(bench / "counts" / "dense.py")
    outcome = harness.Outcome(window_start=0.0, counters={"batch": 2},
                              checks=[], attempted=0, failed=0,
                              extra={"traced_calls": [(3, 2)]})
    steps = _decode.traced_steps(Run(ctx, outcome, None, "cpu"))
    assert steps == [(2000.0 + p, 4000.0 + p) for p in range(5)]
    assert {(kind, pattern) for kind, pattern, _, _ in counted.CALLS} == \
        {("flops", "mamba_shared_attn"), ("bytes", "mamba_shared_attn")}
