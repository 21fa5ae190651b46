#!/usr/bin/env python3
"""Readings for a cell's correctness limit: the program's and its control's.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed, in one process: the cell's own set-up and load for a short
window (`bench/run.py`'s `serve`), then the reference over the same sample
of finished requests as a run's check, reading the served tokens' ranks
and, for each control, the ranks of the tokens that control puts first at
the same positions. A control is the reference computed one bit narrower
than the configuration states: `w-1` (weights: the step that shrinks what
the kernels read) and `a-1` (activations). Each control's readings go
through the run's own comparison (`run.judge`, the cell's limits), which
has to find it not correct. Prints one JSON line per seed. Not part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run  # puts the harness and the program on sys.path
from harness import spec


def controls(a: dict, names: list) -> tuple:
    wb, ab = a["weight_bits"], a["act_bits"]
    table = {"a-1": (wb, ab - 1), "w-1": (wb - 1, ab)}
    return tuple(table[n] for n in names)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", default=["w-1", "a-1"])
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax
    from repro.core import backends
    from repro.launch.serve import use_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    a = cell.config["as_run"]
    for seed in args.seeds:
        t = time.perf_counter()
        sv = run.serve(cell, seed, args.seconds, False, backends.PALLAS, t)
        jax.clear_caches()
        gc.collect()
        t_ref = time.perf_counter()
        smp = run.sample(sv, cell, seed)
        got = run.readings(smp, cell, seed, controls(a, args.controls))
        # each control, put in the program's place, through the run's own
        # comparison: it has to come out not correct
        for name, stats in got.items():
            malformed = smp["malformed"] if name == "program" else 0
            stats["correct"] = run.judge(stats, len(smp["served"]), malformed,
                                         cell.limits)["correct"]
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "tokens": len(smp["served"]),
                          "requests": smp["requests"],
                          "serve_s": t_ref - t,
                          "reference_s": time.perf_counter() - t_ref,
                          **got}), flush=True)
        del sv, smp
        jax.clear_caches()
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
