"""Golden regression: seed-fixed GA/greedy results for one workload per URI
scheme, pinned bitwise and asserted identical across every evaluation
backend that resolves (``serial`` / ``vector`` / ``jax`` —
the same invariance `tests/test_engine.py` pins for the engine itself; an
uninstalled jax shows up as a *skip*, not a hole).

The ``ga_full`` case is FULL-budget-shaped: a paper-scale GA population so
the batched backends see generation-sized miss batches, not toy ones.  The
``ga_cocco`` cases run the benchmark's own design space (a GA co-exploring
the shared buffer size under the energy objective) on its two graphs,
ResNet-50 and the irregular RandWire-A.

Golden artifacts live in ``tests/golden/``; regenerate them after an
*intentional* cost-model or search change with::

    PYTHONPATH=src python tests/test_golden_workloads.py --regen
"""

import json
from pathlib import Path

import pytest
from backend_parity import backend_params

from repro.api import ExploreSpec, GAOptions, GreedyOptions, run
from repro.core import AcceleratorConfig, HWSpace, Objective

KB = 1 << 10
GOLDEN_DIR = Path(__file__).resolve().parent / "golden"
FILE_GRAPH = GOLDEN_DIR / "workload_diamond.json"
# golden artifacts must be machine-independent, so the file: workload's
# absolute path is canonicalized to this repo-relative form before compare
FILE_URI_CANON = "file:tests/golden/workload_diamond.json"

WORKLOADS = {
    "netlib_resnet50": "netlib:resnet50",
    "netlib_randwire_a": "netlib:randwire_a",
    "tpu_gemma3-4b_L0": "tpu:gemma3-4b:0?tokens=512",
    "synthetic_layered24": "synthetic:layered:24?seed=7",
    "file_diamond": f"file:{FILE_GRAPH}",
}

# case key -> (strategy, options, sample_budget).  ``ga_full`` mirrors the
# paper's generation shape (population 64, 20 generations) so the batched
# executors are pinned on generation-sized miss batches too.  ``ga_noc``
# is the multi-core case: a weight-sharing base config, the GA co-exploring
# the core axis (HWSpace.core_candidates), and the trace-derived
# ``noc_p95`` objective — pinning the §5.4.2 NoC charge across backends.
STRATEGIES = {
    "ga": ("ga", GAOptions(population=10), 300),
    "greedy": ("greedy", GreedyOptions(eval_budget=2_000), 300),
    "ga_full": ("ga", GAOptions(population=64), 1_280),
    "ga_noc": ("ga", GAOptions(population=10), 300),
    "ga_cocco": ("ga", GAOptions(population=10), 300),
}

# the Simba-like core and shared-buffer grid of the paper's Sec. 5.3
# co-exploration: 128 KB to 3 MB in 64 KB steps (47 sizes)
COCCO_ACC = AcceleratorConfig(
    glb_bytes=1048576, wbuf_bytes=1179648, shared=False,
    macs_per_cycle=1024, freq_hz=1e9, dram_bytes_per_sec=16e9,
    e_dram_pj_per_byte=100.0, e_mac_pj=0.05, n_cores=1,
    e_noc_pj_per_byte=2.0, weight_share_cores=1)
COCCO_SHARED_CANDIDATES = tuple(range(131072, 3145728 + 1, 65536))

CASES = [(w, s) for w in WORKLOADS for s in ("ga", "greedy")]
CASES += [("synthetic_layered24", "ga_full")]
CASES += [("synthetic_layered24", "ga_noc")]
CASES += [("netlib_resnet50", "ga_cocco"), ("netlib_randwire_a", "ga_cocco")]


def golden_spec(workload_key: str, strategy_key: str) -> ExploreSpec:
    acc = AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=144 * KB)
    strategy, options, budget = STRATEGIES[strategy_key]
    objective = Objective(metric="ema", alpha=None)
    hw = HWSpace(mode="fixed", base=acc)
    if strategy_key == "ga_noc":
        objective = Objective(metric="noc_p95", alpha=0.002)
        hw = HWSpace(
            mode="shared",
            base=AcceleratorConfig(shared=True, weight_share_cores=2,
                                   n_cores=2),
            core_candidates=(2, 4),
        )
    elif strategy_key == "ga_cocco":
        objective = Objective(metric="energy", alpha=0.002)
        hw = HWSpace(mode="shared", base=COCCO_ACC,
                     shared_candidates=COCCO_SHARED_CANDIDATES)
    return ExploreSpec(
        workload=WORKLOADS[workload_key],
        strategy=strategy,
        objective=objective,
        hw=hw,
        sample_budget=budget,
        seed=0,
        options=options,
    )


def canonical_dict(res) -> dict:
    """`ExploreResult` as a parsed-JSON dict (tuples already lowered to
    lists, exactly what a golden file parses back to), with the
    machine-local file: path replaced by its repo-relative form so goldens
    compare bitwise everywhere."""
    d = json.loads(res.to_json())
    local_uri = WORKLOADS["file_diamond"]
    if d["workload"] == local_uri:
        d["workload"] = FILE_URI_CANON
    if d.get("spec") and d["spec"]["workload"] == local_uri:
        d["spec"]["workload"] = FILE_URI_CANON
    return d


def golden_path(workload_key: str, strategy: str) -> Path:
    return GOLDEN_DIR / f"{workload_key}.{strategy}.json"


@pytest.mark.parametrize("backend", backend_params(include_serial=True))
@pytest.mark.parametrize("workload_key,strategy", CASES)
def test_golden_result_pinned_across_backends(workload_key, strategy,
                                              backend):
    spec = golden_spec(workload_key, strategy)
    golden = json.loads(golden_path(workload_key, strategy).read_text())

    got = canonical_dict(run(spec, eval_backend=backend))
    assert got == golden, (
        f"{workload_key}/{strategy} [{backend}] drifted from tests/golden/ "
        f"— if the cost model or search changed intentionally, regenerate "
        f"with `PYTHONPATH=src python tests/test_golden_workloads.py "
        f"--regen`; if only this backend drifted, its arithmetic broke "
        f"bitwise parity")


def test_checked_in_file_workload_is_valid_graph_json():
    from repro.api import build_workload, graph_fingerprint
    from repro.core.graph import graph_from_json

    g = graph_from_json(FILE_GRAPH.read_text())
    assert g.name == "golden_diamond" and g.n == 12
    assert graph_fingerprint(build_workload(f"file:{FILE_GRAPH}")) == \
        graph_fingerprint(g)


def _regen() -> None:
    from repro.api import build_workload
    from repro.core.graph import graph_to_json

    GOLDEN_DIR.mkdir(exist_ok=True)
    if not FILE_GRAPH.exists():
        g = build_workload("synthetic:diamond:12?seed=5")
        g.name = "golden_diamond"
        FILE_GRAPH.write_text(graph_to_json(g))
        print(f"wrote {FILE_GRAPH}")
    for workload_key, strategy in CASES:
        d = canonical_dict(run(golden_spec(workload_key, strategy)))
        path = golden_path(workload_key, strategy)
        path.write_text(json.dumps(d, indent=2) + "\n")
        print(f"wrote {path}  (cost={d['cost']})")


if __name__ == "__main__":
    import sys

    if "--regen" in sys.argv:
        _regen()
    else:
        print(__doc__)
        sys.exit(2)
