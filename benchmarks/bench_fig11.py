"""Paper Fig. 11: graph-partition quality (EMA-opt): Cocco vs Halide-greedy,
Irregular-NN DP, and exact enumeration (small models only), normalized to
greedy.  Claims validated: Cocco matches the enumeration optimum on small
models and beats greedy/DP on the large irregular ones.

All methods run through the unified exploration API as one spec batch per
model (`compare_cached`): every leg is a fully-specified ExploreSpec, so the
sweep is spec-addressed in the result store and resumable, and the legs fan
out over worker processes under ``--jobs``."""

from __future__ import annotations

from dataclasses import replace
from typing import Dict

from repro.api import (
    EnumOptions,
    ExploreSpec,
    GAOptions,
    GreedyOptions,
)
from repro.core import AcceleratorConfig, HWSpace, Objective
from repro.core.netlib import build

from .common import (
    ENUM_STATES,
    GREEDY_EVALS,
    LARGE_MODELS,
    PARTITION_SAMPLES,
    POPULATION,
    SMALL_MODELS,
    Timer,
    compare_cached,
    emit,
    new_evaluator,
)

ENUM_MODELS = {"vgg16", "resnet50", "googlenet", "nasnet"}


def run_model(name: str, samples: int) -> Dict:
    g = build(name)
    ev = new_evaluator(g)
    base = ExploreSpec(
        workload=name,
        objective=Objective(metric="ema", alpha=None),
        hw=HWSpace(mode="fixed", base=AcceleratorConfig()),
        sample_budget=samples,
        seed=0,
    )
    specs = [
        replace(base, strategy="greedy",
                options=GreedyOptions(eval_budget=GREEDY_EVALS)),
        replace(base, strategy="dp", options=None),
    ]
    if name in ENUM_MODELS:
        specs.append(replace(base, strategy="enum",
                             options=EnumOptions(state_budget=ENUM_STATES)))
    # paper §4.3 benefit 4 — "flexible initialization": seed the GA with the
    # other optimizers' results and finetune.  seed_from keeps the seeding
    # inside the spec, so this leg is store-addressable like the rest; the
    # seeds re-run dp/greedy with *default* options, which in reduced mode
    # are >= this benchmark's budgets (so Cocco >= both baselines below and
    # the WARN never fires).  In FULL mode the reported greedy is unbounded
    # while the seed greedy is budget-capped — there the GA's own 400k
    # samples, not the seed, carry the paper's claim, and the WARN check
    # still guards the result.
    specs.append(replace(base, strategy="ga",
                         options=GAOptions(population=POPULATION,
                                           seed_from=("dp", "greedy"))))
    results = {r.strategy: r for r in compare_cached(base, specs,
                                                     graph=g, ev=ev)}

    out: Dict[str, Dict] = {}
    greedy = results["greedy"]
    out["greedy"] = {"ema": greedy.plan.ema_total,
                     "bw": greedy.plan.avg_bandwidth()}
    dp = results["dp"]
    out["dp"] = {"ema": dp.plan.ema_total, "bw": dp.plan.avg_bandwidth()}
    if name in ENUM_MODELS:
        er = results["enum"]
        if er.meta["complete"] and er.plan is not None:
            out["enum"] = {"ema": er.plan.ema_total,
                           "bw": er.plan.avg_bandwidth()}
        else:
            out["enum"] = {"ema": None, "bw": None,
                           "note": f"budget exceeded ({er.meta['states']} states)"}
    cocco = results["ga"]
    out["cocco"] = {"ema": cocco.plan.ema_total,
                    "bw": cocco.plan.avg_bandwidth(),
                    "subgraphs": cocco.n_subgraphs}
    base_ema = out["greedy"]["ema"]
    for k in out:
        if out[k].get("ema"):
            out[k]["ema_norm"] = out[k]["ema"] / base_ema
    return out


def run_all(samples: int = PARTITION_SAMPLES) -> Dict:
    return {name: run_model(name, samples)
            for name in SMALL_MODELS + LARGE_MODELS}


def main() -> None:
    res = run_all()
    for name, methods in res.items():
        t = Timer()
        parts = []
        for m in ("greedy", "dp", "enum", "cocco"):
            if m in methods and methods[m].get("ema_norm") is not None:
                parts.append(f"{m}={methods[m]['ema_norm']:.3f}")
        emit(f"fig11.{name}", t.us, " ".join(parts))
        cocco = methods["cocco"]["ema_norm"]
        others = [methods[m]["ema_norm"] for m in ("greedy", "dp")
                  if methods[m].get("ema_norm")]
        if cocco > min(others) + 1e-6:
            emit(f"fig11.{name}.WARN", t.us,
                 f"cocco {cocco:.3f} worse than best baseline {min(others):.3f}")


if __name__ == "__main__":
    main()
