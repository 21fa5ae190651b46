"""Benchmark harness entry point: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only substring]

Prints ``name,value,derived`` CSV. PUD-side numbers come from the calibrated
DDR4-2400 command model (this container has no FPGA testbed); the kernel
row is an interpret-mode correctness check, not a timing (speed is
measured on the chip by `bench/run.py`); roofline rows aggregate the
multi-pod dry-run artifacts if present.
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from . import kernel_bench, paper_figs, roofline_table, sim_bench
    benches = (paper_figs.ALL + kernel_bench.ALL + sim_bench.ALL
               + roofline_table.ALL)

    print("name,value,derived")

    def emit(name, value, derived=""):
        if isinstance(value, float):
            value = f"{value:.6g}"
        print(f"{name},{value},{derived}")
        sys.stdout.flush()

    for fn in benches:
        if args.only and args.only not in fn.__name__:
            continue
        try:
            fn(emit)
        except Exception as e:  # noqa: BLE001 — report and continue
            emit(f"{fn.__name__}.ERROR", 0, repr(e)[:200])


if __name__ == "__main__":
    main()
