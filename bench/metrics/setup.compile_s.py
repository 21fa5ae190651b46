"""Seconds of JAX compile work in the run's process: tracing to jaxpr,
lowering to MLIR and backend compiles (a persistent-cache hit lies inside
the last), as the union of their spans, so an inner jitted function traced
inside an outer one counts once (`repro.serve.spans`). Read after the
window: it is set-up's where nothing compiles in the window. It overlaps
`setup.weights_s`, whose init compiles each leaf's draw. Nothing where the
program keeps no such counters."""


def read(ctx):
    try:
        from repro.serve import spans
    except ImportError:
        return None
    snap = spans.snapshot()
    if not snap["compiles"]:
        return None
    return snap["compile_wall_s"]
