"""The manifest: every entry of ``BENCHMARK.json`` finds its files by name,
and the entry point refuses what it cannot measure.  Nothing here touches
a chip."""
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, metrics, operands, run

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file_resolves(entry):
    path = ROOT / entry["file"]
    assert path.parent == ROOT / "bench" / "configs"
    config = json.loads(path.read_text())
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    assert hasattr(operands.load(config["operand"]), "make")
    assert {"operands", "matmul", "runs_as"} <= set(config["precision"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_every_file(cell):
    c = harness.load_cell(cell)
    assert c.chips in (1, 4) and c.mesh[0] * c.mesh[1] == c.chips
    e2e = [name for name, _ in c.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for name, _ in c.end_to_end + c.per_layer:
        assert callable(metrics.load(name).read), name
    assert c.limits and all(v >= 0 for v in c.limits.values())


def test_bounds_and_sources():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert set(m["workloads"]) <= set(CELLS)


def test_unknown_device_kind_raises():
    assert harness.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.peaks_for("TPU v99")


def test_unknown_workload_raises():
    with pytest.raises(harness.BenchError, match="unknown workload"):
        harness.load_cell("no-such-cell")


def test_steering_table_is_refused(tmp_path, monkeypatch):
    (tmp_path / "artifacts").mkdir()
    (tmp_path / "artifacts" / "smm_autotune.json").write_text("{}")
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(sys, "path", list(sys.path))
    problem = run.prepare()
    assert problem and "smm_autotune.json" in problem


def test_checkout_without_the_program_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not in this checkout" in proc.stderr
