"""The trace reduction on a small recorded trace (an excerpt of a traced
`qwen2-7b-w2a4.decode` window on one v5e: its device ops and the
benchmark's host spans), checked against an independent sweep."""
import json
import os

import _paths  # noqa: F401
import pytest
from harness import trace

DATA = os.path.join(_paths.BENCH, "testdata", "trace_small.json")


@pytest.fixture(scope="module")
def ev():
    with open(DATA) as f:
        d = json.load(f)
    return {"device": {k: [tuple(e) for e in v]
                       for k, v in d["device"].items()},
            "host": [tuple(e) for e in d["host"]]}


def sweep_busy(events, w0, w1):
    """Covered length by walking every start/end boundary in order."""
    marks = sorted([(max(s, w0), 1) for _, s, d in events if s + d > w0
                    and s < w1] + [(min(s + d, w1), -1) for _, s, d in events
                                    if s + d > w0 and s < w1])
    busy, depth, last = 0, 0, None
    for t, step in marks:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_busy_and_idle_match_a_sweep(ev):
    red = trace.reduce(ev)
    w0, w1 = trace.window(ev["host"])
    plane = next(iter(ev["device"].values()))
    busy = sweep_busy(plane, w0, w1)
    assert red["window_s"] == pytest.approx((w1 - w0) / 1e9)
    assert red["busy_s"] == pytest.approx(busy / 1e9)
    gaps = trace.idle_gaps(trace.clip(plane, w0, w1), w0, w1)
    assert sum(b - a for a, b in gaps) == pytest.approx(w1 - w0 - busy)
    assert 0 < red["busy_s"] < red["window_s"]
    assert len(red["idle_gaps"]) <= 10 and len(red["device_ops"]) <= 10
    longest = max(b - a for a, b in gaps)
    assert red["idle_gaps"][0][1] == pytest.approx(longest / 1e9)
    assert red["idle_gaps"][0][0].startswith(("bench.", "untraced"))


def test_kernel_time_sums_the_named_kernels(ev):
    red = trace.reduce(ev)
    w0, w1 = trace.window(ev["host"])
    plane = trace.clip(next(iter(ev["device"].values())), w0, w1)
    want = sum(d for n, _, d in plane
               if any(k in n for k in trace.BITPLANE_KERNELS))
    assert want > 0
    assert trace.kernel_s(red, trace.BITPLANE_KERNELS) == pytest.approx(
        want / 1e9)
    assert trace.kernel_s(red, ("no such kernel",)) == 0


def test_union_merges_overlaps_and_clip_cuts():
    evs = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 34, 1)]
    assert trace.union(evs) == [[0, 15], [30, 35]]
    assert trace.busy_ns(evs) == 20
    assert trace.clip(evs, 8, 32) == [("a", 8, 2), ("b", 8, 7), ("c", 30, 2)]
    assert trace.idle_gaps(evs, -5, 40) == [(-5, 0), (15, 30), (35, 40)]
