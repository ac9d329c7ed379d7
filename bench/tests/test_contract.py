"""BENCHMARK.json and the harness's last line keep to the contract the
checker reads; every name it holds is a file the harness finds."""
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _text_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level():
    assert list(SPEC) == ["command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_configs():
    used = {w["config"] for w in SPEC["workloads"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _text_ok(c["source"]) and _text_ok(c["why"])
        assert c["file"].startswith("bench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert all(NAME.match(k) and k in cfg for k in c["reduced"])


def test_workloads():
    names = [w["name"] for w in SPEC["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = {(w["config"], w["traffic"]) for w in SPEC["workloads"]}
    assert len(pairs) == len(names)
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(names) // 2)
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _text_ok(w["why"])
        mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        assert (BENCH / "loops" / f"{mix['loop']}.py").is_file()


def _metrics():
    return SPEC["end_to_end"] + SPEC["per_layer"]


def test_metrics_are_named_and_read_by_a_file():
    names = [m["name"] for m in _metrics()]
    assert len(set(names)) == len(names)
    for m in _metrics():
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_setup_another_metric_and_a_layer():
    from bench.lib.cell import select_metrics
    for w in SPEC["workloads"]:
        e2e = {m["name"] for m in select_metrics(SPEC, w["name"], False)}
        layer = select_metrics(SPEC, w["name"], True)
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        # each per-layer metric moves an end-to-end metric of its cells
        assert all(m["moves"] in e2e for m in layer)


def test_per_layer_entries():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    layers = {}
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and _text_ok(m["layer"])
        assert set(m.get("workloads", [])) <= cells
        layers.setdefault(m["layer"], []).append(m["name"])
    assert len(layers) >= 5


def _no_result(proc):
    for line in proc.stdout.strip().splitlines()[-1:]:
        with pytest.raises(ValueError):
            json.loads(line)


def test_no_chip_exits_nonzero_without_result():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ad-batch",
         "--seed", "1", "--seconds", "1"], cwd=ROOT, capture_output=True,
        text=True, timeout=300, env={"JAX_PLATFORMS": "cpu",
                                     "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 3, proc.stderr[-2000:]
    _no_result(proc)


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ep-batch",
         "--seed", "1", "--seconds", "1"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "holds no repro package" in proc.stderr
    _no_result(proc)
