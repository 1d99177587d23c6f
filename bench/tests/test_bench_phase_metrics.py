"""The readers of the program's phase spans, on synthetic runs: the GA
loop's phases and the device call's enqueue and wait."""

from types import SimpleNamespace

import pytest

from bench import common, harness


def _span(i, parent, name, dur, **attrs):
    return SimpleNamespace(index=i, parent=parent, name=name, t0_s=0.0,
                           dur_s=dur, attrs=attrs)


def _device_call(first, parent, put, run, fetch):
    """An ``evaluate_batch`` under ``parent`` whose one device call took
    ``put``, ``run`` and ``fetch`` seconds; its spans start at ``first``."""
    finish = put + run + fetch
    return [
        _span(first, parent, "evaluate_batch", finish + 0.5),
        _span(first + 1, first, "executor.structures", 0.3),
        _span(first + 2, first, "executor.pack", 0.1),
        _span(first + 3, first, "executor.finish", finish),
        _span(first + 4, first + 3, "executor.put", put),
        _span(first + 5, first + 3, "executor.run", run),
        _span(first + 6, first + 3, "executor.fetch", fetch),
        _span(first + 7, first, "executor.stitch", 0.1),
    ]


def _phase_run():
    """One search: init 2 s, generation 0 (4 s) and generation 1 (6 s)."""
    spans = [
        _span(0, -1, "strategy:ga", 13.0, completed=True),
        _span(1, 0, "ga.init", 2.0, population=500),
        _span(2, 0, "ga.generation", 4.0, gen=0),
        _span(3, 2, "ga.repair", 2.0, genomes=500),
    ]
    spans += _device_call(4, 3, 0.002, 0.001, 0.003)      # batch 0.506 s
    spans += [_span(12, 2, "ga.score", 1.5, genomes=500)]
    spans += _device_call(13, 12, 0.004, 0.001, 0.002)    # batch 0.507 s
    spans += [
        _span(21, 0, "ga.samples", 0.2),
        _span(22, 0, "ga.generation", 6.0, gen=1, samples=500),
        _span(23, 22, "ga.variation", 2.4, children=500),
        _span(24, 22, "ga.repair", 2.5, genomes=500),
    ]
    spans += _device_call(25, 24, 0.003, 0.002, 0.003)    # batch 0.508 s
    spans += [
        _span(33, 22, "ga.score", 0.6, genomes=500),
        _span(34, 22, "ga.select", 0.4),
        _span(35, 0, "ga.samples", 0.2),
    ]
    assert [sp.index for sp in spans] == list(range(len(spans)))
    return common.RunData(spans=spans)


GEN = 10.0
READINGS = {
    "ga.init_share": 100.0 * 2.0 / 12.0,
    "ga.variation_share": 100.0 * 2.4 / GEN,
    "ga.repair_share": 100.0 * ((2.0 - 0.506) + (2.5 - 0.508)) / GEN,
    "ga.score_share": 100.0 * ((1.5 - 0.507) + 0.6) / GEN,
    "executor.enqueue_ms": 1e3 * (0.003 + 0.005 + 0.005) / 3,
    "executor.wait_ms": 1e3 * (0.003 + 0.002 + 0.003) / 3,
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_phase_reader(name):
    assert harness.metric_reader(name)(_phase_run()) == \
        pytest.approx(READINGS[name])


@pytest.mark.parametrize("name", sorted(READINGS))
def test_phase_reader_with_nothing_to_read_returns_nothing(name):
    assert harness.metric_reader(name)(common.RunData()) is None


@pytest.mark.parametrize("name", sorted(READINGS))
def test_phase_reader_without_phase_spans_returns_nothing(name):
    """A program that records generations and batches but no phases, as
    before the phase spans, gives these readers nothing to read."""
    phases = {"ga.init", "ga.variation", "ga.repair", "ga.score",
              "ga.select", "ga.samples", "executor.structures",
              "executor.pack", "executor.finish", "executor.put",
              "executor.run", "executor.fetch", "executor.stitch"}
    spans = [
        _span(0, -1, "strategy:ga", 10.0, completed=True),
        _span(1, 0, "ga.generation", 4.0, gen=0),
        _span(2, 1, "evaluate_batch", 1.0),
    ]
    assert not phases & {sp.name for sp in spans}
    assert harness.metric_reader(name)(common.RunData(spans=spans)) is None


def test_enqueue_counts_only_calls_that_finished():
    """A call cut by the window's close after its transfer in is left out."""
    run = _phase_run()
    n = len(run.spans)
    run.spans += [_span(n, 34, "executor.finish", 0.05),
                  _span(n + 1, n, "executor.put", 0.05)]
    assert harness.metric_reader("executor.enqueue_ms")(run) == \
        pytest.approx(READINGS["executor.enqueue_ms"])


def _without(spans, drop):
    """``spans`` with those named in ``drop`` taken out, each child hung
    on its nearest kept ancestor, and the indices renumbered."""
    keep = [sp for sp in spans if sp.name not in drop]
    index = {sp.index: i for i, sp in enumerate(keep)}

    def parent(sp):
        p = sp.parent
        while p >= 0 and spans[p].name in drop:
            p = spans[p].parent
        return index[p] if p >= 0 else -1

    return [_span(index[sp.index], parent(sp), sp.name, sp.dur_s,
                  **sp.attrs) for sp in keep]


def test_host_share_ignores_the_phases_between_generation_and_batch():
    """``ga.host_share`` walks up from each ``evaluate_batch`` to its
    generation, so the same run reads the same with or without the
    ``ga.repair``/``ga.score`` spans in between."""
    read = harness.metric_reader("ga.host_share")
    run = _phase_run()
    flat = common.RunData(spans=_without(run.spans,
                                         {"ga.repair", "ga.score"}))
    assert "evaluate_batch" in {sp.name for sp in flat.spans}
    want = 100.0 * (GEN - 0.506 - 0.507 - 0.508) / GEN
    assert read(run) == pytest.approx(want)
    assert read(flat) == pytest.approx(want)
