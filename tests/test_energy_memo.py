"""Each plan's energy is summed from per-subgraph terms memoized at their
hardware point (``PlanCost.energy_pj``): bit-identical to recomputing every
term, never served at another point, invisible in a ``SubgraphCost``'s
fields, equality, repr and serialized forms, and counted once per plan."""

import copy
import os
import pickle
import random
import sys
import threading
from dataclasses import asdict, fields, replace

import pytest

from repro.api.result import plan_from_dict, plan_to_dict
from repro.core import (
    AcceleratorConfig,
    CachedEvaluator,
    HWSpace,
    Objective,
    PlanCost,
    SubgraphCost,
    random_partition,
)
from repro.core.netlib import build
from repro.obs import Recorder, recording

N_PLANS = 240


@pytest.fixture(scope="module", params=["resnet50", "randwire_a"])
def graph(request):
    return build(request.param)


def _old_energy(plan):
    return sum(s.energy_pj(plan.acc) for s in plan.subgraphs)


@pytest.mark.parametrize("mode", ["fixed", "separate", "shared"])
def test_memoized_energy_is_bit_identical(graph, mode):
    """A few hundred seeded plans through one evaluator: partitions and
    hardware points drawn from small pools, so most terms are memo hits and
    some cached subgraphs are read at several points."""
    rng = random.Random(f"energy-memo-{mode}")
    hw = HWSpace(mode=mode)
    accs = [hw.sample(rng) for _ in range(4)]
    accs.append(replace(accs[0], e_dram_pj_per_byte=60.0))
    pool = [random_partition(graph, rng) for _ in range(24)]
    ev = CachedEvaluator(graph)
    obj = Objective(metric="energy", alpha=0.002)
    plans = ev.plan_batch([(rng.choice(pool), rng.choice(accs))
                           for _ in range(N_PLANS)])
    for plan in plans + plans:  # the second pass reads every term memoized
        old = _old_energy(plan)
        new = plan.energy_pj
        assert new == old and new.hex() == old.hex()
        assert obj.cost(plan, plan.acc) == \
            plan.acc.buf_size_total + 0.002 * old


def _cost():
    return SubgraphCost(nodes=(0, 1), ema_in=4096, ema_out=2048,
                        ema_w=65536, macs=10 ** 6, footprint=8192,
                        weight_resident=65536, glb_access_bytes=123457,
                        wbuf_access_bytes=65536, noc_bytes=640)


def test_each_hardware_point_gets_its_own_term():
    """The evaluator's key leaves out the energy constants, so one cached
    cost can be read at points that differ only there, or only in sharing."""
    sc = _cost()
    base = AcceleratorConfig(glb_bytes=512 * 1024, wbuf_bytes=576 * 1024)
    pricier = replace(base, e_dram_pj_per_byte=150.0)
    shared = replace(base, shared=True)
    for acc in (base, pricier, base, shared, base, shared, pricier):
        assert PlanCost([sc], acc).energy_pj == sc.energy_pj(acc)
    assert len({sc.energy_pj(a) for a in (base, pricier, shared)}) == 3


def test_the_memo_is_not_part_of_the_cost():
    sc, fresh = _cost(), _cost()
    acc = AcceleratorConfig()
    assert PlanCost([sc], acc).energy_pj == fresh.energy_pj(acc)
    assert "_energy_memo" in vars(sc)
    assert "_energy_memo" not in {f.name for f in fields(sc)}
    assert asdict(sc) == asdict(fresh)
    assert repr(sc) == repr(fresh)
    assert sc == fresh
    assert pickle.dumps(sc) == pickle.dumps(fresh)
    for clone in (pickle.loads(pickle.dumps(sc)), copy.copy(sc),
                  copy.deepcopy(sc)):
        assert clone == sc and "_energy_memo" not in vars(clone)
    stored = plan_to_dict(PlanCost([sc], acc))
    assert stored == plan_to_dict(PlanCost([fresh], acc))
    assert plan_from_dict(stored).energy_pj == fresh.energy_pj(acc)


def test_counters_count_terms_and_misses_per_plan():
    a, b = _cost(), replace(_cost(), nodes=(2,))
    acc = AcceleratorConfig()
    rec = Recorder()
    with recording(rec):
        PlanCost([a, b], acc).energy_pj              # 2 terms, 2 misses
        PlanCost([a, b, a], acc).energy_pj           # 3 terms, 0 misses
        PlanCost([a], replace(acc, e_mac_pj=0.1)).energy_pj  # 1, 1
    assert rec.counters["plan.energy_terms"] == 6
    assert rec.counters["plan.energy_term_misses"] == 3
    with recording(rec):
        PlanCost([a], acc).energy_pj                 # a moved to the new point
    assert rec.counters["plan.energy_terms"] == 7
    assert rec.counters["plan.energy_term_misses"] == 4


def test_threads_racing_on_shared_costs_read_their_own_point():
    """Readers at two points race on the same costs, each memo being
    overwritten by the other point's readers: every sum is its own point's.
    The memo is one tuple, so no reader can pair a point with another's
    term."""
    costs = [replace(_cost(), nodes=(i,), glb_access_bytes=1000 + 7 * i)
             for i in range(64)]
    accs = [AcceleratorConfig(), AcceleratorConfig(e_dram_pj_per_byte=150.0)]
    want = [_old_energy(PlanCost(costs, acc)) for acc in accs]
    wrong = []

    def reader(k):
        plan = PlanCost(costs, accs[k % 2])
        for _ in range(200):
            if plan.energy_pj != want[k % 2]:
                wrong.append(k)

    threads = [threading.Thread(target=reader, args=(k,))
               for k in range(min(2 * (os.cpu_count() or 2), 32))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not wrong
