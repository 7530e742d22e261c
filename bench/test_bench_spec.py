"""BENCHMARK.json keeps to the benchmark's contract, and a cell, a
configuration, a traffic mix and a metric are added by adding files and
entries only, then found by name."""
from __future__ import annotations

import json
import re
import shutil

import numpy as np
import pytest

from bench.conftest import ROOT
from bench.spec import Benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys_and_command(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"][:2] == ["python3", "-m"]
    assert 1 <= spec["run_seconds"] <= 51
    for p in spec["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_bounds(spec):
    names = [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    metrics = spec["end_to_end"] + spec["per_layer"]
    for entry in spec["configs"] + spec["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    assert len(set(c["name"] for c in spec["configs"])) == len(spec["configs"])
    assert len(set(w["name"] for w in spec["workloads"])) == len(
        spec["workloads"])
    assert len(set(m["name"] for m in metrics)) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and m["layer"] and "\n" not in m["layer"]
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_cell_resolves_and_reports_what_it_must(spec):
    bench = Benchmark(ROOT)
    configs = {c["name"] for c in spec["configs"]}
    pairs = set()
    for w in spec["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell = bench.cell(w["name"])
        assert int(np.prod(cell.traffic["grid"])) == w["chips"]
        e2e = [m.name for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert all(m.moves in e2e for m in cell.per_layer)
    used = {w["config"] for w in spec["workloads"]}
    assert used == configs
    files = [c["file"] for c in spec["configs"]]
    assert len(set(files)) == len(files)
    for c in spec["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["source"] == c["source"]
        assert set(c["reduced"]) == set(data["reduced"])
        assert set(data["check"]) == {"pattern_diff", "repeated_columns",
                                      "max_rel_err"}


def test_a_full_check_fits_its_time_at_24_cells(spec):
    runs = 2 + 14 * 24
    total = runs * (spec["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


ER_GENERATOR = """
import numpy as np
from bench.operand import canonical, from_scipy

def generate(log2_n, per_column, *, structure, values, relabel):
    n = 1 << log2_n
    rows = structure.integers(0, n, n * per_column)
    cols = structure.integers(0, n, n * per_column)
    m = canonical(rows, cols, np.ones(len(rows), np.float32), n)
    m.data = values.uniform(0.5, 1.0, m.nnz).astype(np.float32)
    return from_scipy(m)
"""

A_AT_OPERATION = """
import numpy as np
import scipy.sparse as sps
from bench.operand import from_scipy
from bench.operations import square

def operands(op):
    return op, from_scipy(sps.csr_matrix(op.to_scipy(np.float32).T))

program = square.program
"""


def test_cell_config_traffic_and_metric_added_as_files_only(tmp_path):
    """A new generator, configuration, operation, traffic mix and metric
    are new files only; the new cell is found by name and runs on the CPU
    through the harness, correct against its reference."""
    import jax

    from bench import run
    from bench.conftest import TEST_PEAKS
    from bench.test_bench_window import TickingClock

    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}

    # new files: a generator, a configuration, an operation, a traffic mix
    # and a per-layer metric
    (root / "bench/generators/erdos_renyi.py").write_text(ER_GENERATOR)
    config = {
        "name": "er-2e8", "source": "https://doi.org/10.1137/0804020 (ER)",
        "generator": "erdos_renyi", "structure_seed": 0,
        "params": {"log2_n": 8, "per_column": 4}, "reduced": {},
        "check": {"pattern_diff": 0, "repeated_columns": 0,
                  "max_rel_err": 1e-4}}
    (root / "bench/configs/er-2e8.json").write_text(json.dumps(config))
    (root / "bench/operations/a_at.py").write_text(A_AT_OPERATION)
    traffic = json.loads((root / "bench/traffic/square-sync.json").read_text())
    traffic.update(name="a-at", operation="a_at", local_path="hash")
    (root / "bench/traffic/a-at.json").write_text(json.dumps(traffic))
    (root / "bench/metrics/batches_per_call.py").write_text(
        "def read(ctx):\n"
        "    return len(ctx.window.batches) / ctx.window.calls\n")
    # new entries in BENCHMARK.json
    spec["configs"].append({
        "name": "er-2e8", "source": config["source"],
        "file": "bench/configs/er-2e8.json", "reduced": [],
        "why": "a uniform random graph"})
    spec["workloads"].append({
        "name": "er-2e8.a-at", "config": "er-2e8", "traffic": "a-at",
        "chips": 1, "why": "A·Aᵀ on the hash path"})
    spec["per_layer"].append({
        "name": "batches_per_call", "unit": "batches", "better": "lower",
        "source": "program_counter", "layer": "planner",
        "moves": "products_per_s", "workloads": ["er-2e8.a-at"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    bench = Benchmark(root)
    cell = bench.cell("er-2e8.a-at")
    assert cell.config["params"]["log2_n"] == 8
    assert cell.traffic["local_path"] == "hash"
    assert [m.name for m in cell.per_layer] == ["batches_per_call"]
    result, ctx = run.run_cell(
        cell, 7, 1.5, False, jax.devices(), TEST_PEAKS,
        t_process=0.0, budget=80_000, clock=TickingClock(1.0))
    assert result["correct"] is True and result["attempted"] == 2
    assert ctx.counts.products(np.arange(256)) > 0
    assert cell.per_layer[0].reader.read(ctx) == 2.0
    # the old cells are found as before, and no existing file changed
    old = bench.cell("protein-2e18.square-sync")
    assert "batches_per_call" not in [m.name for m in old.per_layer]
    assert [m.name for m in old.end_to_end] == ["products_per_s", "setup_s"]
    for rel, data in before.items():
        assert (root / rel).read_bytes() == data, rel


def test_unknown_names_are_errors(tmp_path):
    bench = Benchmark(ROOT)
    with pytest.raises(KeyError):
        bench.cell("no-such.cell")
    with pytest.raises(FileNotFoundError):
        bench.traffic("no-such-traffic")
