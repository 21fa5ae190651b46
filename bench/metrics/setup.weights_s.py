"""Seconds the program took to make its weights ready to serve: its
`init` (seeded draw and per-leaf quantization), `quantize` (the engine's
pass over the leaves) and `place` (the simulated DRAM placement, with its
fallback) set-up phases, as `repro.serve.spans` counts them in memory.
These phases run only in set-up, so the count read after the window is
set-up's. Nothing where the program keeps no such counters."""

PHASES = ("init", "quantize", "place")


def read(ctx):
    try:
        from repro.serve import spans
    except ImportError:
        return None
    phases = spans.snapshot()["phases"]
    if not any(p in phases for p in PHASES):
        return None
    return sum(phases[p]["s"] for p in PHASES if p in phases)
