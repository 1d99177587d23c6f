"""The harness finds everything by name, keeps BENCHMARK.json's rules, and
refuses to run off the chip."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = harness.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _new_files(root: Path) -> None:
    """A benchmark tree that only adds files and entries."""
    bench_dir = root / "bench"
    (bench_dir / "configs").mkdir(parents=True)
    (bench_dir / "traffic").mkdir()
    (bench_dir / "metrics").mkdir()
    config = json.loads((ROOT / "bench" / "configs" /
                         "resnet50.json").read_text())
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(
        dict(config, name="tiny", workload="netlib:tiny", population=4)))
    (bench_dir / "traffic" / "trickle.json").write_text(json.dumps(
        {"kind": "oneshot", "eval_backend": "jax"}))
    (bench_dir / "metrics" / "tiny.count.py").write_text(
        "def read(run):\n    return len(run.device_calls) or None\n")
    doc = json.loads(json.dumps(BENCH))
    doc["configs"].append({"name": "tiny", "source": "x",
                           "file": "bench/configs/tiny.json", "reduced": [],
                           "why": "x"})
    doc["workloads"].append({"name": "tiny.trickle", "config": "tiny",
                             "traffic": "trickle", "chips": 1, "why": "x"})
    doc["per_layer"].append({"name": "tiny.count", "unit": "requests",
                             "better": "higher", "source": "program_counter",
                             "layer": "device", "moves": "samples_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))


def test_new_cell_traffic_and_metric_are_found_by_name(tmp_path):
    _new_files(tmp_path)
    bench = harness.load_benchmark(tmp_path)
    cell, config, traffic = harness.load_cell(bench, "tiny.trickle", tmp_path,
                                              tmp_path / "bench")
    assert config["population"] == 4 and traffic["kind"] == "oneshot"
    assert harness.driver_class(traffic["kind"]).__module__ == \
        "bench.drive_oneshot"
    # a per-layer metric with no workloads list goes wherever its end-to-end
    # metric is reported
    names = [m["name"] for m in harness.cell_metrics(bench, "tiny.trickle",
                                                     "per_layer")]
    assert "tiny.count" not in names  # samples_per_s does not list the cell
    assert "tiny.count" in [m["name"] for m in harness.cell_metrics(
        bench, "resnet50.oneshot", "per_layer")]
    read = harness.metric_reader("tiny.count", tmp_path / "bench")
    assert read(harness.importlib.import_module("bench.common").RunData(
        device_calls=[None, None])) == 2


def test_unknown_names_are_refused(tmp_path):
    _new_files(tmp_path)
    bench = harness.load_benchmark(tmp_path)
    with pytest.raises(harness.BenchError):
        harness.load_cell(bench, "nope.cell", tmp_path, tmp_path / "bench")
    with pytest.raises(harness.BenchError):
        harness.driver_class("no_such_kind")
    with pytest.raises(harness.BenchError):
        harness.metric_reader("no.such.metric")


@pytest.mark.parametrize("change", [
    {"core_candidates": [1, 2, 4]},                       # a setting not passed on
    {"objective": {"metric": "noc_p95", "alpha": None}},  # not scored
    {"objective": {"metric": "energy"}},
])
def test_a_configuration_the_harness_cannot_honour_is_refused(tmp_path,
                                                              change):
    _new_files(tmp_path)
    path = tmp_path / "bench" / "configs" / "tiny.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), **change)))
    with pytest.raises(harness.BenchError, match="tiny"):
        harness.load_cell(harness.load_benchmark(tmp_path), "tiny.trickle",
                          tmp_path, tmp_path / "bench")


def _run(cwd: Path, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "resnet50.oneshot",
         "--seed", str(2**33), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _result_lines(stdout: str):
    return [ln for ln in stdout.splitlines() if ln.lstrip().startswith("{")]


def test_off_the_chip_it_exits_nonzero_with_no_result():
    proc = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)
    assert "TPU" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)


def test_benchmark_json_keeps_its_rules():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (ROOT / BENCH["command"][1]).exists()
    assert 1 <= BENCH["run_seconds"] <= 51
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert (ROOT / c["file"]).exists()
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in e2e
    used = set()
    for cell in BENCH["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["config"] in configs and cell["chips"] in (1, 4)
        assert len(cell["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").exists()
        used.add(cell["config"])
        reported = {m["name"] for m in harness.cell_metrics(
            BENCH, cell["name"], "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        layer = harness.cell_metrics(BENCH, cell["name"], "per_layer")
        assert layer and all(m["moves"] in reported for m in layer)
    assert used == set(configs)
    names = [x["name"] for part in ("configs", "workloads", "end_to_end",
                                    "per_layer") for x in BENCH[part]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
