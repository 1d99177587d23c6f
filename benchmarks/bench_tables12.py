"""Paper Tables 1-2: hardware-mapping co-exploration with separate / shared
buffers.  Methods: fixed-HW (S/M/L) + partition-only, two-step RS+GA / GS+GA,
co-opt SA and Cocco.  Cost = Formula 2 (BUF_SIZE + alpha * energy),
alpha = 0.002, energy metric.  Claim: co-opt (Cocco) <= two-step <= fixed.

Every method is a registry strategy on the same ExploreSpec family, with one
shared CachedEvaluator per model.  Each model runs as two spec batches
(the HW searches, then the partition-only final-cost runs at every chosen
hardware point), so the whole table is store-addressed/resumable and
parallel under ``--jobs``."""

from __future__ import annotations

from dataclasses import replace
from typing import Dict

from repro.api import ExploreSpec, GAOptions, TwoStepOptions
from repro.core import AcceleratorConfig, HWSpace, Objective
from repro.core.netlib import build

from .common import (
    COOPT_MODELS,
    COOPT_SAMPLES,
    POPULATION,
    Timer,
    compare_cached,
    emit,
    new_evaluator,
)

KB = 1024
ALPHA = 0.002

FIXED = {
    "separate": {"S": (512 * KB, 576 * KB), "M": (1024 * KB, 1152 * KB),
                 "L": (2048 * KB, 2304 * KB)},
    "shared": {"S": (576 * KB, 0), "M": (1152 * KB, 0), "L": (2304 * KB, 0)},
}


def part_spec(g, acc, samples) -> ExploreSpec:
    """Paper §5.3.1: after choosing HW, run partition-only at that point."""
    return ExploreSpec(
        workload=g.name,
        strategy="ga",
        objective=Objective(metric="energy", alpha=None),
        hw=HWSpace(mode="fixed", base=acc),
        sample_budget=samples,
        seed=1,
        options=GAOptions(population=POPULATION),
    )


def run_model(name: str, mode: str, samples: int) -> Dict:
    g = build(name)
    return _run_model(g, new_evaluator(g), mode, samples)


def _run_model(g, ev, mode: str, samples: int) -> Dict:
    coopt = ExploreSpec(
        workload=g.name,
        strategy="ga",
        objective=Objective(metric="energy", alpha=ALPHA),
        hw=HWSpace(mode=mode),
        sample_budget=samples,
        seed=4,
        options=GAOptions(population=POPULATION),
    )
    part_budget = max(samples // 2, 1000)

    # phase 1: the hardware searches (two-step x2, SA, Cocco) as one batch
    search_specs = {
        "rs_ga": replace(coopt, strategy="two_step", seed=2,
                         options=TwoStepOptions(
                             sampler="random", capacity_samples=4,
                             samples_per_capacity=max(samples // 4, 500))),
        "gs_ga": replace(coopt, strategy="two_step", seed=2,
                         options=TwoStepOptions(
                             sampler="grid", capacity_samples=4,
                             samples_per_capacity=max(samples // 4, 500))),
        "sa": replace(coopt, strategy="sa", seed=3, options=None),
        "cocco": coopt,
    }
    searched = dict(zip(search_specs,
                        compare_cached(coopt, list(search_specs.values()),
                                       graph=g, ev=ev)))

    # phase 2: Formula-2 final cost at every chosen hardware point
    accs = {
        f"fixed_{tag}": AcceleratorConfig(glb_bytes=a, wbuf_bytes=w,
                                          shared=(mode == "shared"))
        for tag, (a, w) in FIXED[mode].items()
    }
    accs.update({tag: res.acc for tag, res in searched.items()})
    final_specs = [part_spec(g, acc, part_budget) for acc in accs.values()]
    finals = dict(zip(accs, compare_cached(final_specs[0], final_specs,
                                           graph=g, ev=ev)))

    out: Dict[str, Dict] = {}
    for tag, acc in accs.items():
        out[tag] = {
            "glb_kb": acc.glb_bytes // KB,
            "wbuf_kb": acc.wbuf_bytes // KB,
            "cost": acc.buf_size_total + ALPHA * finals[tag].plan.energy_pj,
        }
    return out


def run_all(mode: str, samples: int = COOPT_SAMPLES) -> Dict:
    return {m: run_model(m, mode, samples) for m in COOPT_MODELS}


def main() -> None:
    for mode, table in (("separate", "table1"), ("shared", "table2")):
        res = run_all(mode)
        for name, methods in res.items():
            t = Timer()
            best_base = min(v["cost"] for k, v in methods.items()
                            if k != "cocco")
            c = methods["cocco"]["cost"]
            emit(f"{table}.{name}", t.us,
                 f"cocco={c:.3e} best_baseline={best_base:.3e} "
                 f"improvement={(1 - c / best_base) * 100:.1f}% "
                 f"size={methods['cocco']['glb_kb']}KB+"
                 f"{methods['cocco']['wbuf_kb']}KB")


if __name__ == "__main__":
    main()
