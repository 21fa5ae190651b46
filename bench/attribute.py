#!/usr/bin/env python3
"""One traced run of a cell, with its window attributed by the program's
own names.

    python3 bench/attribute.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as `bench/run.py --trace 1` does and prints the same line,
with an "attribution" block beside it (`harness/attribution.py`): idle
device time by the `serve.*` or `bench.*` host span that covers it, host
milliseconds per tick (a `serve.tick` less its `serve.wait`), device time
by model region and by region and op kind, and the set-up counters of
`repro.serve.spans`: each phase's seconds, compile seconds when the window
opened, and the compile events inside the window's ticks. After the run's
metrics are read, it compiles the tick executables once more to read each
instruction's region from their HLO text.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import types

import run as bench_run
from harness import attribution, spec, trace


def _compiles(snap: dict) -> int:
    return sum(v["count"] for v in snap["compiles"].values())


def attribute_cell(cell: spec.Cell, seed: int, seconds: float,
                   **run_kw) -> dict:
    """The traced run's line, with the "attribution" block. A program
    without `repro.serve.spans` gets the trace's part of the block.
    `run_kw` goes to `run.run_cell` (a test's backend and cache)."""
    import jax
    import jax.numpy as jnp
    try:
        from repro.serve import spans
    except ImportError:
        spans = None
    state: dict = {"marks": []}

    def patch(batcher):
        state["batcher"] = batcher
        if spans is None:
            return
        tick = batcher.tick

        def counted():
            before = spans.snapshot()
            t0 = time.perf_counter()
            tick()
            state["marks"].append((t0, _compiles(before),
                                   _compiles(spans.snapshot()),
                                   before["compile_wall_s"]))
        batcher.tick = counted

    def context(cell, sv, kind, ev):
        state["ev"], state["sv"] = ev, sv
        return run_context(cell, sv, kind, ev)

    def check(sv, cell, seed):
        # the metrics are read: compile the tick executables for their
        # HLO text, then free the program before the reference check
        b = state.pop("batcher")
        lanes = len(b.lanes)
        state["hlo"] = [fn.lower(
            b.params, b.cache, jnp.zeros((lanes, trip), jnp.int32),
            jnp.zeros((lanes,), jnp.int32), jnp.zeros((lanes,), jnp.int32)
        ).compile().as_text() for trip, fn in sorted(b._tick_fns.items())]
        del b
        jax.clear_caches()
        gc.collect()
        return run_check(sv, cell, seed)

    run_context, run_check = bench_run._context, bench_run.check
    bench_run.trace_mod = types.SimpleNamespace(
        load_events=attribution.load_events, reduce=trace.reduce)
    bench_run._context, bench_run.check = context, check
    try:
        line = bench_run.run_cell(cell, seed, seconds, True, patch=patch,
                                  **run_kw)
    finally:
        bench_run.trace_mod = trace
        bench_run._context, bench_run.check = run_context, run_check
    att = attribution.attribute(state["ev"],
                                attribution.op_regions(state["hlo"]))
    if spans is not None:
        sv = state["sv"]
        t0, t1 = sv.window_open, sv.window_open + sv.window_s
        inside = [m for m in state["marks"] if t0 <= m[0] <= t1]
        att.update(
            compiles_in_window=sum(after - before
                                   for _, before, after, _ in inside),
            compile_s_at_open=inside[0][3] if inside else None,
            setup_phases={k: v["s"]
                          for k, v in spans.snapshot()["phases"].items()})
    line["attribution"] = att
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"attribute: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    print(json.dumps(attribute_cell(cell, args.seed, args.seconds)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
