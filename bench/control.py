#!/usr/bin/env python3
"""The control and the planted faults that ``correct`` has to catch.

Each is a context manager that breaks the timed path underneath a run:

* ``f32`` — the control: the reference's objective, computed in float32
  (the precision below the float64 the configurations state), put in the
  place of the program's plan cost (``Objective.cost``).  A device-side
  cost reduction in float32 is the step it stands for.
* ``lane`` — one answer altered where it is produced: the kernel's first
  lane reports one more byte of weight traffic.
* ``half`` — half of the batch left out: the kernel computes the first
  half of its lanes and returns zeros for the rest.
* ``stale`` — a step that returns its state unchanged: the kernel returns
  the previous call's outputs.
* ``footprint`` — a wrong answer of the structure half: the executor hands
  the kernel every footprint one byte high, so the lanes agree with their
  own inputs and only a cost re-derived from the graph shows it.

On the chip, at a cell's own size (not one of the benchmark's runs)::

    python3 bench/control.py --control f32 --workload <cell> --seed <n> --seconds <s>
"""

import time

T_START = time.perf_counter()

import contextlib  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    import pathsetup  # noqa: F401  (the harness and the program)

import numpy as np  # noqa: E402


@contextlib.contextmanager
def _patched(owner, name, value):
    old = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, old)


def f32():
    from repro.core.ga import Objective

    from bench import reference

    def cost(self, plan, acc):
        terms = [(s.ema_total, s.glb_access_bytes, s.wbuf_access_bytes,
                  s.noc_bytes, s.macs) for s in plan.subgraphs]
        fields = {k: getattr(acc, k) for k in (
            "glb_bytes", "wbuf_bytes", "shared", "e_dram_pj_per_byte",
            "e_noc_pj_per_byte", "e_mac_pj")}
        return reference.objective(terms, fields, self.metric, self.alpha,
                                   np.float32)

    return _patched(Objective, "cost", cost)


def _kernel(transform):
    from repro.kernels import finish_batch

    inner = finish_batch.finish_cost_batch

    def broken(*args):
        return transform(args, inner)

    return _patched(finish_batch, "finish_cost_batch", broken)


def lane():
    def transform(args, inner):
        out = [np.array(o) for o in inner(*args)]
        if len(out[2]):
            out[2][0] += 1
        return tuple(out)

    return _kernel(transform)


def half():
    def transform(args, inner):
        n = len(args[0])
        kept = [np.asarray(a)[: (n + 1) // 2] for a in args]
        part = inner(*kept)
        return tuple(np.concatenate([p, np.zeros(n - len(p), dtype=p.dtype)])
                     for p in part)

    return _kernel(transform)


def stale():
    last = []

    def transform(args, inner):
        n = len(args[0])
        fresh = inner(*args)
        prev = last[0] if last else fresh
        last[:] = [fresh]
        return tuple(np.resize(p, n) for p in prev)

    return _kernel(transform)


def footprint():
    from repro.core.engine import JaxExecutor

    inner = JaxExecutor._finish_arrays

    def broken(self, fp, *rest):
        return inner(self, fp + 1, *rest)

    return _patched(JaxExecutor, "_finish_arrays", broken)


CONTROLS = {"f32": f32, "lane": lane, "half": half, "stale": stale,
            "footprint": footprint}


def main(argv) -> int:
    import argparse
    import json

    from bench import harness

    ap = argparse.ArgumentParser(description="run a cell with a control or "
                                             "a planted fault")
    ap.add_argument("--control", choices=sorted(CONTROLS), required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    harness.prepare_env()
    with CONTROLS[args.control]():
        try:
            doc = harness.run_cell(harness.load_benchmark(), args.workload,
                                   args.seed, args.seconds, False, T_START)
        except harness.BenchError as err:
            harness.say(f"error: {err}")
            return 1
    print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
