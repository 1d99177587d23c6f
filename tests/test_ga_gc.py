"""The GA search runs with CPython's cyclic collector paused
(``core/ga.py:gc_paused``): the collector is restored as it was found, on
errors and across overlapping searches too; the pause changes no result; and
its premise holds, that a search leaves no cyclic garbage behind."""

import gc
import threading
from contextlib import contextmanager

import pytest

from repro.core import CachedEvaluator, HWSpace, Objective, ga, run_ga
from repro.core.engine import backend_status, make_executor
from repro.core.netlib import resnet50
from repro.obs import Recorder, recording

TIMEOUT_S = 60


@pytest.fixture(scope="module")
def graph():
    return resnet50()


def _search(g, seed=1, backend=None):
    ev = CachedEvaluator(g, executor=make_executor(backend)) \
        if backend else None
    return run_ga(g, Objective(metric="energy", alpha=0.002),
                  HWSpace(mode="shared"), sample_budget=300, population=30,
                  seed=seed, ev=ev)


@contextmanager
def _collector(on):
    """The collector switched ``on`` or off for the block, then as it was."""
    was = gc.isenabled()
    (gc.enable if on else gc.disable)()
    try:
        yield
    finally:
        (gc.enable if was else gc.disable)()


class _Gate(Recorder):
    """A recorder that, on opening ``ga.generation`` 1, tells ``inside`` and
    waits for ``release``: holds its search inside the pause."""

    def __init__(self):
        super().__init__()
        self.inside = threading.Event()
        self.release = threading.Event()

    def _open(self, name, attrs):
        if name == "ga.generation" and attrs.get("gen") == 1:
            self.inside.set()
            assert self.release.wait(TIMEOUT_S)
        return super()._open(name, attrs)


class _Abort(Exception):
    pass


class _Aborting(Recorder):
    """A recorder whose span ``_open`` raises on the first generation, as the
    benchmark's window recorder raises ``WindowClosed`` into a search."""

    def _open(self, name, attrs):
        if name == "ga.generation":
            raise _Abort(name)
        return super()._open(name, attrs)


@pytest.mark.parametrize("on", [True, False])
def test_run_ga_leaves_the_collector_as_found(graph, on):
    with _collector(on):
        _search(graph)
        assert gc.isenabled() is on


@pytest.mark.parametrize("on", [True, False])
def test_collector_restored_when_the_search_raises(graph, on):
    rec = _Aborting()
    with _collector(on):
        with recording(rec), pytest.raises(_Abort):
            _search(graph)
        assert gc.isenabled() is on
    # an aborted search still reports its collections
    assert rec.counters["ga.gc_collections"] == 0


def test_overlapping_searches_keep_the_collector_paused(graph):
    gates = [_Gate(), _Gate()]
    errors = []

    def search(gate):
        try:
            with recording(gate):
                _search(graph)
        except Exception as err:
            errors.append(err)

    with _collector(True):
        threads = [threading.Thread(target=search, args=(gate,))
                   for gate in gates]
        for t in threads:
            t.start()
        try:
            for gate in gates:
                assert gate.inside.wait(TIMEOUT_S)
            assert not gc.isenabled()
            gates[0].release.set()
            threads[0].join(TIMEOUT_S)
            assert not threads[0].is_alive()
            assert not gc.isenabled()      # the second search still runs
        finally:
            for gate in gates:
                gate.release.set()
            for t in threads:
                t.join(TIMEOUT_S)
        assert not any(t.is_alive() for t in threads)
        assert not errors
        assert gc.isenabled()


def test_nested_pauses(graph):
    with _collector(True):
        with ga.gc_paused():
            with ga.gc_paused():
                assert not gc.isenabled()
            assert not gc.isenabled()
            _search(graph)                 # a search inside a pause
            assert not gc.isenabled()
        assert gc.isenabled()


def test_pause_never_enables_a_collector_the_caller_disabled():
    with _collector(False):
        with ga.gc_paused():
            with ga.gc_paused():
                pass
        assert not gc.isenabled()


def test_gc_collections_counter_reads_zero(graph):
    rec = Recorder()
    with _collector(True), recording(rec):
        _search(graph)
    assert rec.counters["ga.gc_collections"] == 0


def test_pause_changes_no_result(graph, monkeypatch):
    """The same search with the collector forced on, at a threshold that
    makes it collect often: the same cost, groups in the same order, the
    same buffer point and history."""

    def key(res):
        return (res.best.cost, [list(s) for s in res.best.groups],
                res.best.acc, res.history, res.samples, res.evaluations)

    paused = key(_search(graph, seed=4))

    @contextmanager
    def forced_on():
        threshold = gc.get_threshold()
        gc.set_threshold(50, 2, 2)
        try:
            with _collector(True):
                yield
        finally:
            gc.set_threshold(*threshold)

    monkeypatch.setattr(ga, "gc_paused", forced_on)
    rec = Recorder()
    with recording(rec):
        collected = key(_search(graph, seed=4))
    assert rec.counters["ga.gc_collections"] > 0   # the collector did run
    assert collected == paused


@pytest.mark.parametrize("backend", ["serial", "vector", "jax"])
def test_a_search_leaves_no_cyclic_garbage(graph, backend):
    """The pause's premise: with the collector off, a whole search, recorder
    on, leaves nothing for ``gc.collect()`` to find.  If the GA's data ever
    form cycles, this fails and the pause is no longer free."""
    ok, why = backend_status(backend)
    if not ok:
        pytest.skip(why)
    _search(graph, seed=0, backend=backend)     # warm-up: jax compiles
    with _collector(False):
        gc.collect()
        with recording(Recorder()):
            _search(graph, seed=5, backend=backend)
        assert gc.collect() == 0
