"""`python -m repro` CLI: explore / compare / spec+result artifacts."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.api import ExploreResult, ExploreSpec
from repro.api.cli import main

REPO_ROOT = Path(__file__).resolve().parents[1]


def test_compare_smoke(capsys):
    rc = main(["compare", "--workload", "vgg16",
               "--strategies", "greedy,dp,ga",
               "--budget", "300", "--opt", "population=10"])
    assert rc == 0
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.strip()]
    header = lines[0].split()
    assert header[:3] == ["rank", "strategy", "cost"]
    body = "\n".join(lines[1:])
    for name in ("greedy", "dp", "ga"):
        assert name in body
    assert "best:" in out


def test_explore_writes_artifacts(tmp_path, capsys):
    out_path = tmp_path / "result.json"
    spec_path = tmp_path / "spec.json"
    rc = main(["explore", "--workload", "vgg16", "--strategy", "greedy",
               "--save-spec", str(spec_path), "--out", str(out_path)])
    assert rc == 0
    assert "vgg16[greedy]" in capsys.readouterr().out

    spec = ExploreSpec.from_json(spec_path.read_text())
    assert spec.workload == "vgg16" and spec.strategy == "greedy"

    res = ExploreResult.from_json(out_path.read_text())
    assert res.feasible
    assert res.spec == spec


def test_explore_from_spec_file_reproduces(tmp_path, capsys):
    spec_path = tmp_path / "spec.json"
    out_a = tmp_path / "a.json"
    out_b = tmp_path / "b.json"
    assert main(["explore", "--workload", "vgg16", "--strategy", "ga",
                 "--budget", "200", "--opt", "population=10",
                 "--save-spec", str(spec_path), "--out", str(out_a)]) == 0
    assert main(["explore", "--spec", str(spec_path),
                 "--out", str(out_b)]) == 0
    a = ExploreResult.from_json(out_a.read_text())
    b = ExploreResult.from_json(out_b.read_text())
    assert a.cost == b.cost
    assert a.groups == b.groups


def test_explore_profile_prints_structure_counters(tmp_path, capsys):
    rc = main(["explore", "--workload", "vgg16", "--strategy", "ga",
               "--budget", "200", "--opt", "population=10", "--profile"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "profile: wall" in out
    assert "derive_schedule" in out
    assert "canonical" in out and "raw" in out
    # profiled run with a store: the stored artifact carries no timings,
    # and the replay says so instead of printing a bogus profile
    store = tmp_path / "store"
    args = ["explore", "--workload", "vgg16", "--strategy", "greedy",
            "--profile", "--store-dir", str(store),
            "--out", str(tmp_path / "r.json")]
    assert main(args) == 0
    first = capsys.readouterr().out
    assert "profile: wall" in first
    stored = ExploreResult.from_json((tmp_path / "r.json").read_text())
    assert "profile" in stored.meta  # --out sees the in-memory profile...
    raw = json.loads(next(store.glob("*.json")).read_text())
    assert "profile" not in raw["meta"]  # ...the store never does
    assert main(args) == 0
    assert "store hit — no search ran" in capsys.readouterr().out


def test_explore_struct_cache_dir_round_trip(tmp_path, capsys):
    cache_dir = tmp_path / "structs"
    args = ["explore", "--workload", "vgg16", "--strategy", "ga",
            "--budget", "200", "--opt", "population=10", "--profile",
            "--struct-cache-dir", str(cache_dir),
            "--out", str(tmp_path / "cold.json")]
    assert main(args) == 0
    cold_out = capsys.readouterr().out
    assert "disk hits" in cold_out
    assert any(cache_dir.glob("*.json"))  # the cold run populated the cache
    cold = ExploreResult.from_json((tmp_path / "cold.json").read_text())
    warm_args = list(args)
    warm_args[-1] = str(tmp_path / "warm.json")
    assert main(warm_args) == 0
    warm = ExploreResult.from_json((tmp_path / "warm.json").read_text())
    assert warm.meta["profile"]["structure_misses"] == 0  # fully warm
    assert warm.meta["profile"]["structure_disk_hits"] > 0
    # the warm run is bitwise-identical to the cold one (minus timings)
    cold.meta.pop("profile"), warm.meta.pop("profile")
    assert warm.to_json() == cold.to_json()


def test_compare_out_is_ranked_json(tmp_path, capsys):
    out_path = tmp_path / "cmp.json"
    rc = main(["compare", "--workload", "vgg16", "--strategies", "greedy,dp",
               "--out", str(out_path)])
    assert rc == 0
    rows = json.loads(out_path.read_text())
    assert len(rows) == 2
    costs = [r["cost"] for r in rows]
    assert costs == sorted(costs)
    # each row is a loadable ExploreResult
    for r in rows:
        assert ExploreResult.from_dict(r).feasible


def test_bad_arguments_exit_nonzero():
    with pytest.raises(SystemExit):
        main(["explore"])                      # neither --spec nor --workload
    with pytest.raises(SystemExit):
        main(["explore", "--workload", "vgg16", "--strategy", "nope"])
    with pytest.raises(SystemExit):
        main(["explore", "--workload", "vgg16", "--opt", "population"])


def test_unknown_eval_backend_exits_2_and_lists_backends(capsys):
    from repro.core.engine import BACKENDS

    assert BACKENDS == ("serial", "vector", "jax")
    for bogus in ("bogus", "process"):
        rc = main(["explore", "--workload", "vgg16", "--strategy", "greedy",
                   "--budget", "100", "--eval-backend", bogus])
        assert rc == 2
        err = capsys.readouterr().err
        assert f"unknown eval backend {bogus!r}" in err
        for backend in BACKENDS:
            assert backend in err


def test_unavailable_jax_backend_exits_2_with_why(capsys, monkeypatch):
    """When jax is not importable the CLI reports the import failure and
    how to fix it, instead of a traceback."""
    import repro.core.engine as engine

    monkeypatch.setattr(engine, "_JAX_STATUS",
                        (False, "ModuleNotFoundError: No module named 'jax'"))
    rc = main(["explore", "--workload", "vgg16", "--strategy", "greedy",
               "--budget", "100", "--eval-backend", "jax"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "'jax' is unavailable" in err
    assert "No module named 'jax'" in err
    assert "pip install jax" in err


def test_explore_eval_backend_jax_matches_serial(tmp_path, capsys):
    from backend_parity import available_backends

    if "jax" not in available_backends():
        pytest.skip("jax not installed")
    serial_out = tmp_path / "serial.json"
    jax_out = tmp_path / "jax.json"
    base = ["explore", "--workload", "vgg16", "--strategy", "ga",
            "--budget", "200", "--opt", "population=10"]
    assert main(base + ["--out", str(serial_out)]) == 0
    assert main(base + ["--eval-backend", "jax",
                        "--out", str(jax_out)]) == 0
    capsys.readouterr()
    assert jax_out.read_text() == serial_out.read_text()


def test_module_entrypoint_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(REPO_ROOT / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "repro", "compare", "--workload", "vgg16",
         "--strategies", "greedy,dp", "--budget", "200"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "rank" in proc.stdout and "best:" in proc.stdout


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """``chip_smoke.py`` checks the device first and never carries on on
    the CPU: nonzero exit, no result line."""
    pytest.importorskip("jax")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO_ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_store_ls_and_gc_cli(tmp_path, capsys):
    store_dir = tmp_path / "store"
    rc = main(["explore", "--workload", "vgg16", "--strategy", "greedy",
               "--store-dir", str(store_dir)])
    assert rc == 0
    capsys.readouterr()

    assert main(["store", "ls", "--store-dir", str(store_dir)]) == 0
    out = capsys.readouterr().out
    assert "vgg16" in out and "greedy" in out and "1 entries" in out

    assert main(["store", "gc", "--store-dir", str(store_dir),
                 "--max-bytes", "0"]) == 0
    out = capsys.readouterr().out
    assert "evicted 1 entries" in out

    assert main(["store", "ls", "--store-dir", str(store_dir)]) == 0
    assert "0 entries" in capsys.readouterr().out


def test_workloads_ls_cli(capsys):
    from repro.core.netlib import list_models

    assert main(["workloads", "ls"]) == 0
    out = capsys.readouterr().out
    assert "netlib:resnet50" in out
    assert "tpu:<config>:<layer>" in out
    assert "synthetic:layered:<n>[?seed=S]" in out
    assert "file:<path>.json" in out

    assert main(["workloads", "ls", "--scheme", "netlib",
                 "--uris-only"]) == 0
    out = capsys.readouterr().out
    assert out.split() == [f"netlib:{n}" for n in list_models()]

    # --uris-only is script-friendly: every line is a concrete URI the
    # resolver accepts (no templates like tpu:<arch>:0..N)
    from repro.api import parse_workload
    assert main(["workloads", "ls", "--uris-only"]) == 0
    uris = capsys.readouterr().out.split()
    assert uris and all(".." not in u and "<" not in u for u in uris)
    for uri in uris:
        parse_workload(uri)
    assert "tpu:gemma3-4b:0" in uris and "tpu:gemma3-4b:33" in uris

    assert main(["workloads", "ls", "--scheme", "bogus"]) == 2
    assert "unknown workload scheme" in capsys.readouterr().err


def test_workloads_ls_json_is_machine_readable(capsys):
    from repro.api import parse_workload

    assert main(["workloads", "ls", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert set(doc) == {"schemes", "workloads"}
    names = {s["name"] for s in doc["schemes"]}
    assert {"netlib", "tpu", "synthetic", "file"} <= names
    for s in doc["schemes"]:
        assert set(s) == {"name", "syntax", "description", "stable"}
    assert doc["workloads"], "concrete URIs expected"
    for w in doc["workloads"]:
        assert set(w) == {"uri", "scheme", "description"}
        assert "<" not in w["uri"] and ".." not in w["uri"]
        parse_workload(w["uri"])                  # every entry resolves

    # --scheme filters both sections
    assert main(["workloads", "ls", "--json", "--scheme", "netlib"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert [s["name"] for s in doc["schemes"]] == ["netlib"]
    assert all(w["scheme"] == "netlib" for w in doc["workloads"])


def test_trace_cli_exports_deterministic_valid_json(tmp_path, capsys):
    import sys

    sys.path.insert(0, str(REPO_ROOT / "scripts"))
    try:
        from check_trace_schema import validate_trace_dict
    finally:
        sys.path.pop(0)

    out_a, out_b = tmp_path / "a.json", tmp_path / "b.json"
    base = ["trace", "synthetic:layered:16?seed=2", "--strategy", "greedy"]
    assert main(base + ["--out", str(out_a)]) == 0
    out = capsys.readouterr().out
    assert "cross-validation OK" in out and "bandwidth: peak=" in out
    assert main(base + ["--out", str(out_b)]) == 0
    capsys.readouterr()
    # byte-identical across runs for a fixed seed
    assert out_a.read_text() == out_b.read_text()

    doc = json.loads(out_a.read_text())
    assert validate_trace_dict(doc) == []
    assert doc["meta"]["validation"]["ok"] is True
    tot = doc["totals"]
    assert tot["dram_bytes"] == tot["dram_in"] + tot["dram_out"]
    assert tot["dram_bytes"] == \
        doc["meta"]["validation"]["total_analytical_bytes"]

    # --steps-per-subgraph coalesces the timeline but preserves every total
    out_c = tmp_path / "c.json"
    assert main(base + ["--steps-per-subgraph", "2",
                        "--out", str(out_c)]) == 0
    capsys.readouterr()
    coarse = json.loads(out_c.read_text())
    assert validate_trace_dict(coarse) == []
    assert coarse["totals"] == doc["totals"]
    assert len(coarse["steps"]) < len(doc["steps"])


def test_trace_cli_replays_archived_plan(tmp_path, capsys):
    res_path = tmp_path / "res.json"
    assert main(["explore", "--workload", "synthetic:diamond:10?seed=2",
                 "--strategy", "greedy", "--out", str(res_path)]) == 0
    capsys.readouterr()
    assert main(["trace", "--plan", str(res_path)]) == 0
    out = capsys.readouterr().out
    assert "synthetic:diamond:10?seed=2[greedy]" in out
    assert "cross-validation OK" in out

    # a conflicting workload URI alongside --plan is rejected, not ignored
    with pytest.raises(SystemExit, match="cannot be combined"):
        main(["trace", "netlib:resnet50", "--plan", str(res_path)])
    # ...and so is a positional URI that disagrees with --workload
    with pytest.raises(SystemExit, match="conflicting workloads"):
        main(["trace", "synthetic:chain:8?seed=1",
              "--workload", "netlib:vgg16"])


def test_explore_accepts_workload_uris(tmp_path, capsys):
    out_path = tmp_path / "res.json"
    rc = main(["explore", "--workload", "synthetic:layered:12?seed=1",
               "--strategy", "greedy", "--out", str(out_path)])
    assert rc == 0
    assert "synthetic:layered:12?seed=1[greedy]" in capsys.readouterr().out
    res = ExploreResult.from_json(out_path.read_text())
    assert res.feasible and res.workload == "synthetic:layered:12?seed=1"

    assert main(["explore", "--workload", "bogus:thing"]) == 2
    assert "unknown workload scheme" in capsys.readouterr().err


def test_store_cli_without_dir_exits():
    import os
    env_had = os.environ.pop("REPRO_STORE_DIR", None)
    try:
        with pytest.raises(SystemExit, match="store maintenance"):
            main(["store", "ls"])
    finally:
        if env_had is not None:
            os.environ["REPRO_STORE_DIR"] = env_had


def test_store_ls_json_is_machine_readable(tmp_path, capsys):
    store_dir = tmp_path / "store"
    assert main(["explore", "--workload", "synthetic:chain:6?seed=1",
                 "--strategy", "greedy", "--budget", "100",
                 "--store-dir", str(store_dir)]) == 0
    capsys.readouterr()
    assert main(["store", "ls", "--store-dir", str(store_dir),
                 "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["root"] == str(store_dir)
    assert doc["count"] == 1 and doc["total_bytes"] > 0
    (entry,) = doc["entries"]
    assert len(entry["key"]) == 64
    assert entry["workload"] == "synthetic:chain:6?seed=1"
    assert entry["strategy"] == "greedy"
    assert entry["size"] > 0 and entry["mtime"] > 0
    # full keys round-trip into --seed-from-store / store maintenance
    assert (store_dir / f"{entry['key']}.json").is_file()


def test_zoo_build_dry_run_ls_verify(tmp_path, capsys):
    zoo_dir = tmp_path / "zoo"
    grid = ["--zoo-dir", str(zoo_dir),
            "--workloads", "synthetic:chain:6?seed=1",
            "--strategies", "greedy", "--objectives", "ema,energy:0.002",
            "--budget", "100"]

    assert main(["zoo", "build", "--dry-run"] + grid) == 0
    out = capsys.readouterr().out
    assert "2 zoo specs (dry run" in out and "energy:0.002" in out
    assert not zoo_dir.exists()                 # dry run builds nothing

    assert main(["zoo", "ls", "--json"] + grid) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["archived"] == 0 and doc["missing"] == 2

    assert main(["zoo", "build"] + grid) == 0
    assert "2 built" in capsys.readouterr().out
    assert main(["zoo", "build"] + grid) == 0   # resumable: all replay
    assert "2 already archived" in capsys.readouterr().out

    assert main(["zoo", "ls", "--json"] + grid) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["archived"] == 2 and doc["missing"] == 0
    assert all(r["status"] == "archived" for r in doc["rows"])

    assert main(["zoo", "verify", "--zoo-dir", str(zoo_dir)]) == 0
    assert "2 artifacts verified clean" in capsys.readouterr().out


def test_explore_seed_from_store_warm_starts(tmp_path, capsys):
    store_dir = tmp_path / "store"
    base = ["--workload", "synthetic:layered:8?seed=3", "--strategy", "ga",
            "--opt", "population=10", "--store-dir", str(store_dir)]
    assert main(["explore", "--budget", "200"] + base) == 0
    capsys.readouterr()
    assert main(["store", "ls", "--store-dir", str(store_dir),
                 "--json"]) == 0
    key = json.loads(capsys.readouterr().out)["entries"][0]["key"]

    # a unique >=8-char prefix resolves; the seeded spec addresses a NEW
    # store entry (seed_from_keys is part of the spec hash)
    assert main(["explore", "--budget", "400",
                 "--seed-from-store", key[:12]] + base) == 0
    capsys.readouterr()
    assert main(["store", "ls", "--store-dir", str(store_dir),
                 "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["count"] == 2

    # guard rails: needs a store, a ga-family strategy, and no --spec
    with pytest.raises(SystemExit, match="resolves keys against a store"):
        main(["explore", "--workload", "x", "--strategy", "ga",
              "--no-store", "--seed-from-store", key[:12]])
    with pytest.raises(SystemExit, match="seed_from_keys"):
        main(["explore", "--workload", "x", "--strategy", "greedy",
              "--store-dir", str(store_dir), "--seed-from-store", key[:12]])
    assert main(["explore", "--budget", "200",
                 "--seed-from-store", "deadbeef"] + base) == 2
    assert "no store entry matches" in capsys.readouterr().err


def test_serve_plans_cli_help_and_missing_store():
    with pytest.raises(SystemExit):            # argparse --help exits 0
        main(["serve-plans", "--help"])
    env_had = os.environ.pop("REPRO_STORE_DIR", None)
    try:
        with pytest.raises(SystemExit, match="serve-plans needs"):
            main(["serve-plans", "--port", "0"])
    finally:
        if env_had is not None:
            os.environ["REPRO_STORE_DIR"] = env_had
