"""The program's spans and counters, read the way the benchmark reads them:
a tiny batcher's ticks come back from a profiler trace as one `serve.tick`
each, tiled by its children; the attribution's readers give hand-computed
results on synthetic events; the program's spans leave the accepted
per-layer metrics of a recorded trace bit-identical; the set-up readers
read the program's counters, and nothing where the program has none."""
import dataclasses
import json
import os
import sys

import _paths  # noqa: F401
import jax
import pytest
from harness import attribution, spec, trace

DATA = os.path.join(_paths.BENCH, "testdata", "trace_small.json")
CHILDREN = ("serve.admit", "serve.plan", "serve.stage", "serve.dispatch",
            "serve.wait", "serve.commit")


def test_each_tick_is_one_span_tiled_by_its_children(tmp_path):
    from repro.configs import tiny_config
    from repro.models.model import param_defs
    from repro.models.params import init_params
    from repro.serve.scheduler import ContinuousBatcher, Request
    cfg = dataclasses.replace(tiny_config("llama2-7b"), dtype="float32")
    b = ContinuousBatcher(cfg, init_params(param_defs(cfg),
                                           jax.random.PRNGKey(0)),
                          max_seq=32, lanes=2, prefill_chunk=4)
    b.submit(Request(rid=0, prompt=[1, 2, 3], max_new=3))
    b.run()                                     # compile outside the trace
    for rid, n in ((1, 6), (2, 2), (3, 5)):
        b.submit(Request(rid=rid, prompt=list(range(1, n + 1)), max_new=3))
    t0 = b.ticks
    with jax.profiler.trace(str(tmp_path)):
        b.run()
    host = attribution.load_events(str(tmp_path))["host"]
    ticks = sorted((s, s + d) for n, s, d in host if n == "serve.tick")
    assert len(ticks) == b.ticks - t0 > 0
    kids = [(n, s, s + d) for n, s, d in host if n in CHILDREN]
    admits = 0
    for a, z in ticks:
        inside = sorted((s, e, n) for n, s, e in kids if a <= s and e <= z)
        names = [n for _, _, n in inside]
        admits += names.count("serve.admit")
        assert names[names.count("serve.admit"):] == list(CHILDREN[1:])
        assert all(e <= s2 for (_, e, _), (s2, _, _) in
                   zip(inside, inside[1:]))        # in order, no overlap
        covered = sum(e - s for s, e, _ in inside)
        assert covered >= 0.9 * (z - a)            # tiled up to glue code
    assert admits == 3
    assert len(kids) == sum(1 for n, _, _ in kids if n != "serve.admit") \
        + admits


def test_label_breaks_ties_to_the_shortest_span():
    host = [("bench.window", 0, 100), ("bench.tick", 10, 50),
            ("serve.tick", 12, 40), ("serve.dispatch", 20, 10),
            ("serve.wait", 30, 20)]
    assert attribution.label(host, 22, 28) == "serve.dispatch"
    assert attribution.label(host, 31, 49) == "serve.wait"
    # a gap across two children goes to the span that covers it whole
    assert attribution.label(host, 26, 40) == "serve.tick"
    assert attribution.label(host, 55, 58) == "bench.tick"
    assert attribution.label(host, 70, 80) == "untraced host work"



def test_idle_is_split_among_the_innermost_spans():
    host = [("bench.window", 0, 100), ("bench.tick", 10, 50),
            ("serve.tick", 12, 40), ("serve.dispatch", 20, 10),
            ("serve.wait", 30, 20)]
    assert attribution.innermost(host) == [
        (10, 12, "bench.tick"), (12, 20, "serve.tick"),
        (20, 30, "serve.dispatch"), (30, 50, "serve.wait"),
        (50, 52, "serve.tick"), (52, 60, "bench.tick")]
    got = attribution.idle_by_span(
        host, [(18, 34), (51, 58), (70, 80), (40, 42)])
    assert got == [["serve.dispatch", 10e-9, 1, 0],
                   ["untraced host work", 10e-9, 1, 0],
                   ["bench.tick", 6e-9, 1, 0], ["serve.wait", 6e-9, 2, 2e-9],
                   ["serve.tick", 3e-9, 2, 0]]


def test_longest_gaps_name_the_ops_around_them():
    ev = {"host": [("bench.window", 0, 100), ("serve.wait", 10, 50)],
          "device": {"/device:TPU:0": [("fusion", 0, 20), ("while", 5, 25),
                                       ("copy-start", 40, 5),
                                       ("convert", 80, 10)]},
          "ops": {}}
    got = attribution.attribute(ev, {})
    assert got["idle_gaps"] == [
        ["serve.wait", 35e-9, "copy-start", "convert"],
        ["serve.wait", 10e-9, "fusion", "copy-start"],
        ["untraced host work", 10e-9, "convert", None]]
    assert got["idle_s"] == pytest.approx(55e-9)


def test_host_time_per_tick_leaves_out_the_wait():
    host = [("serve.tick", 100, 50), ("serve.wait", 110, 30),
            ("serve.tick", 200, 40), ("serve.wait", 205, 10),
            ("serve.tick", 900, 50), ("serve.wait", 910, 30)]
    assert attribution.tick_host_s(host, 0, 500) == [20e-9, 30e-9]


def test_heads_and_regions_of_hlo_text():
    trace_name = ("%copy-start.23 = (s32[4]{0:T(128)S(1)}, s32[4]{0:T(128)},"
                  " u32[]{:S(2)}) copy-start(s32[4]{0:T(128)} %steps.1)")
    assert attribution.head(trace_name) == "copy-start.23 copy-start"
    compiled = [
        '  %fusion.7 = bf16[4,512]{1,0:T(4,128)(2,1)} fusion(%p), '
        'kind=kLoop, calls=%f, metadata={op_name="jit(run)/while/body/'
        'closed_call/attention/kv_write/scatter" stack_frame_id=3}',
        '  ROOT %convert.2 = bf16[8]{0} convert(%x), metadata={op_name='
        '"jit(run)/while/body/closed_call/embed/convert_element_type"}',
        '  %fusion.9 = f32[2]{0} fusion(%q), kind=kLoop, calls=%g, '
        'metadata={op_name="jit(run)/while/body/ffn/jit(_run_codes)/'
        'pallas_call"}',
        '  %copy.1 = f32[2]{0} copy(%q)']
    other = ['  %fusion.9 = f32[2]{0} fusion(%q), metadata={op_name='
             '"jit(run)/while/body/closed_call/freeze_lanes/select_n"}']
    got = attribution.op_regions(["\n".join(compiled), "\n".join(other)])
    assert got == {"fusion.7 fusion": "attention/kv_write",
                   "convert.2 convert": "embed",
                   "fusion.9 fusion": "ffn or freeze_lanes"}
    assert attribution.scope_of("jit(run)/while/body/sample/argmax") == \
        "sample"
    assert attribution.scope_of("jit(run)/iota") == attribution.UNSCOPED


def _recorded():
    with open(DATA) as f:
        d = json.load(f)
    return {"device": {k: [tuple(e) for e in v]
                       for k, v in d["device"].items()},
            "host": [tuple(e) for e in d["host"]]}


ACCEPTED = ("sched.lane_occupancy_pct.decode", "model_step.mfu_pct",
            "kernel.bitplane_gemv.roofline_pct",
            "kernel.bitplane_gemv.busy_share_pct", "device.idle_pct")


def test_program_spans_leave_the_accepted_metrics_bit_identical():
    ev = _recorded()
    (_, w0, wd), = [e for e in ev["host"] if e[0] == "bench.window"]
    spans, t, k = [], w0 + 40_000, 0
    while t + 2_000_000 < w0 + wd:          # one tick every 2.2 ms
        spans += [("serve.tick", t, 2_000_000), ("serve.plan", t, 50_000),
                  ("serve.stage", t + 50_000, 1_100_000),
                  ("serve.dispatch", t + 1_150_000, 400_000),
                  ("serve.wait", t + 1_550_000, 440_000),
                  ("serve.commit", t + 1_990_000, 10_000)]
        t, k = t + 2_200_000, k + 1
    assert k > 5
    with_spans = dict(ev, host=ev["host"] + spans)
    counters = {"occupancy_ticks": {4: 120, 3: 20}, "lanes": 4}
    steps = {"step_roofline_s": 0.002, "kernels": {"bitplane_gemv": {
        "launches_per_step": 113, "kernel_step_s": 0.0021}}}
    read = {}
    for name, e in (("plain", ev), ("spans", with_spans)):
        ctx = {"counters": counters, "window_s": 0.035, "steps": steps,
               "trace": trace.reduce(e)}
        read[name] = [spec.metric_reader(m)(ctx) for m in ACCEPTED]
    assert all(v is not None for v in read["plain"])
    assert read["plain"] == read["spans"]


def test_set_up_readers_read_the_program_counters(monkeypatch):
    from repro.serve import spans
    with spans.phase("init"):
        pass
    with spans.phase("place"):
        pass
    jax.jit(lambda x: x * 3 + 1)(1.0)          # one compile, at least
    snap = spans.snapshot()
    weights = spec.metric_reader("setup.weights_s")({})
    assert weights == pytest.approx(sum(
        snap["phases"][p]["s"] for p in ("init", "quantize", "place")
        if p in snap["phases"]))
    compile_s = spec.metric_reader("setup.compile_s")({})
    assert compile_s >= snap["compile_wall_s"] > 0
    # a program without the counters: the readers give nothing
    import repro.serve
    monkeypatch.delattr(repro.serve, "spans")
    monkeypatch.setitem(sys.modules, "repro.serve.spans", None)
    assert spec.metric_reader("setup.weights_s")({}) is None
    assert spec.metric_reader("setup.compile_s")({}) is None


def test_attribute_runs_a_cell(monkeypatch):
    """`bench/attribute.py` end to end at a test's size on the CPU (the
    peaks table knows no CPU, so the test lends it the v5e's)."""
    import attribute
    from repro.core import backends
    from test_bench_checks import CELL, tiny_cell
    peaks = spec.peaks
    monkeypatch.setattr(spec, "peaks", lambda kind: peaks("TPU v5 lite"))
    line = attribute.attribute_cell(tiny_cell(CELL), 2**31 + 7, 2.0,
                                    backend=backends.JNP, cache=False)
    att = line["attribution"]
    assert line["correct"]
    assert att["ticks"] > 0 and att["host_ms_per_tick"] > 0
    assert att["bench_ticks"] >= att["ticks"]
    assert att["compiles_in_window"] == 0
    assert att["compile_s_at_open"] > 0
    assert {"init", "quantize", "place"} <= set(att["setup_phases"])
    assert {"setup.compile_s", "setup.weights_s"} <= set(line["metrics"])
