"""The trace reduction, on hand-made device timelines.

A check against a recorded chip trace (a trimmed xplane of a traced run
of `7pt-var.n512.t64`, made by ``trim_trace.py``, reduced again to what
that run printed) is still to come: PERF.md, Open questions.
"""

import pytest

from chipbench import trace


def _dev(*events):
    return trace.Device("/device:TPU:0", sorted(events))


def test_nested_events_count_once_and_gaps_are_named():
    dev = _dev((0.0, 100.0, "mwd_7pt-var", trace.KERNEL),
               (10.0, 20.0, "inner", trace.GLUE),
               (150.0, 170.0, "fusion.1", trace.GLUE))
    att = trace.attribute(dev, (0.0, 200.0))
    assert att.busy == 120.0
    assert att.by_category == {trace.KERNEL: 90.0, trace.GLUE: 30.0}
    assert att.gaps == [(100.0, 150.0), (170.0, 200.0)]
    tr = trace.Trace(devices=[dev], spans=[(95.0, 160.0, "bench.wait"),
                                           (160.0, 200.0, "bench.call")],
                     window=(0.0, 200.0))
    bd = trace.breakdown(tr, [att])
    assert [g[0] for g in bd["idle_gaps"]] == ["bench.wait", "bench.call"]
    assert [g[1] for g in bd["idle_gaps"]] == pytest.approx([50e-9, 30e-9])
    assert bd["device_ops"][0] == ["mwd_7pt-var", pytest.approx(90e-9)]


def test_exposed_collective_is_what_no_other_op_covers():
    dev = _dev((0.0, 50.0, "mwd_25pt-const", trace.KERNEL),
               (40.0, 80.0, "collective-permute-done", trace.COLLECTIVE),
               (70.0, 75.0, "fusion.3", trace.GLUE))
    att = trace.attribute(dev, (0.0, 100.0))
    # 50..70 and 75..80 run the collective alone
    assert att.exposed_collective == 25.0
    assert att.busy == 80.0


@pytest.mark.parametrize("name,cat", [
    ("mwd_7pt-var", trace.KERNEL), ("collective-permute-start.2",
                                    trace.COLLECTIVE),
    ("all-reduce.1", trace.COLLECTIVE), ("fusion.12", trace.GLUE),
    ("copy.3", trace.GLUE)])
def test_classify(name, cat):
    assert trace.classify(name, {}) == cat


def test_classify_reads_stats():
    assert trace.classify("custom-call.4", {"long_name": "mwd_25pt-const"}) \
        == trace.KERNEL
