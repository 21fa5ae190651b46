"""Operations and bytes of one inner decode step, over a block family.

A configuration's family (`families/<family>.py`, see `spec.family`) counts
its own step from shapes: `step_flops`, `step_bytes` and `kernel_calls`,
each given the window's program counters, so a family whose least bytes
depend on what the program did (which experts it routed to) can read them.
Every count is a minimum: what any implementation of the step has to read
and compute, so a time derived from it can only lie at or below the time
the chip really took. The packed-weight arithmetic is that of the
program's `serving_bytes`, without `col_sum`, which the kernels never read.
"""
from __future__ import annotations

import math


def packed_bytes(n: int, m: int, bits: int) -> int:
    """HBM bytes of one packed weight: its bit planes and its scales."""
    return bits * math.ceil(n / 32) * m * 4 + m * 4


def step_roofline_s(family, m: dict, bits: int, positions: list, peak: dict,
                    counters: dict | None = None) -> float:
    """The least time one inner step can take on a chip with `peak`."""
    return max(family.step_flops(m, positions, counters)
               / peak["flops_per_s"],
               family.step_bytes(m, bits, positions, counters)
               / peak["hbm_bytes_per_s"])


def kernel_roofline_s(family, m: dict, bits: int, act_bits: int, rows: int,
                      peak: dict, counters: dict | None = None) -> dict:
    """{kernel set: {"launches_per_step", "kernel_step_s"}} of one executed
    step at `rows` activation rows: each set's launches, and their least
    time, each launch the larger of its bytes over bandwidth and its ops
    over peak."""
    calls = family.kernel_calls(m, bits, act_bits, rows, counters)
    out = {}
    for kset in dict.fromkeys(k for _, k, _, _ in calls):
        mine = [(b, o) for _, k, b, o in calls if k == kset]
        out[kset] = {"launches_per_step": len(mine), "kernel_step_s": sum(
            max(o / peak["flops_per_s"], b / peak["hbm_bytes_per_s"])
            for b, o in mine)}
    return out


def kernel_calls(m: dict, bits: int, act_bits: int, rows: int) -> list:
    """The dense family's `kernel_calls` with no counters, under the name
    through which the program's own tests count a benchmark configuration's
    launches. The harness asks a cell's family."""
    from harness import spec
    return spec.family("dense").kernel_calls(m, bits, act_bits, rows)
