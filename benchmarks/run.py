# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark orchestrator.

    PYTHONPATH=src python -m benchmarks.run [--only fig3,fig11,...]
        [--store-dir runs/store] [--jobs N] [--no-store]
        [--eval-backend serial|vector|jax]

Reduced sample budgets by default (REPRO_BENCH_FULL=1 for the paper's
400k/50k budgets).  Emits `name,us_per_call,derived` CSV rows.

``--store-dir`` (default ``runs/store``, or ``$REPRO_STORE_DIR``) keeps a
spec-addressed cache of every search the partition benchmarks perform, so an
interrupted sweep — or a re-run to re-plot — replays finished specs from disk
instead of re-searching; ``--no-store`` disables it.  ``--jobs N`` runs
independent strategies of one benchmark point in N worker processes.
"""

from __future__ import annotations

import argparse
import importlib
import os
import time
import traceback

# bench name -> module (imported at dispatch time: the kernel/serve/roofline
# benches need jax, and a lazy registry keeps --help and the cost-model
# benches working without it)
BENCHES = {
    "fig3": "bench_fig3",
    "fig11": "bench_fig11",
    "tables12": "bench_tables12",
    "fig12_13_14": "bench_fig12_13_14",
    "table3": "bench_table3",
    "workloads": "bench_workloads",
    "trace": "bench_trace",
    "serve": "bench_serve",
    "kernels": "bench_kernels",
    "roofline": "bench_roofline",
}


def _bench_main(name: str):
    module = importlib.import_module(f"benchmarks.{BENCHES[name]}")
    return module.main


def main() -> None:
    from . import common

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    ap.add_argument("--store-dir",
                    default=os.environ.get("REPRO_STORE_DIR", "runs/store"),
                    help="spec-addressed result store for resumable sweeps "
                         "(default: runs/store)")
    ap.add_argument("--no-store", action="store_true",
                    help="always search from scratch")
    ap.add_argument("--jobs", type=int, default=1,
                    help="worker processes for independent strategy runs")
    ap.add_argument("--eval-backend", default=None,
                    help="evaluation-engine executor: serial | vector | "
                         "jax (default: serial)")
    args = ap.parse_args()
    if args.eval_backend is not None:
        from repro.core.engine import backend_status

        ok, why = backend_status(args.eval_backend)
        if not ok:
            raise SystemExit(f"error: {why}")
    common.configure(store_dir=None if args.no_store else args.store_dir,
                     jobs=args.jobs, eval_backend=args.eval_backend)
    names = list(BENCHES) if not args.only else args.only.split(",")
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        raise SystemExit(f"error: unknown bench {unknown}; "
                         f"valid: {', '.join(BENCHES)}")
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        t0 = time.time()
        try:
            _bench_main(name)()
        except Exception as e:
            failures += 1
            print(f"{name}.ERROR,{(time.time() - t0) * 1e6:.0f},"
                  f"{type(e).__name__}: {e}")
            traceback.print_exc()
        print(f"{name}.total,{(time.time() - t0) * 1e6:.0f},done")
    if common.STORE is not None:
        print(f"# {common.STORE.stats()}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
