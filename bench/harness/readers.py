"""Reading helpers shared by more than one metric file."""


def lane_occupancy_pct(ctx):
    """Σ occupancy·count over (lanes · Σ count) of the window's inner
    steps; nothing when the window ran none."""
    occ = ctx["counters"]["occupancy_ticks"]
    steps = sum(occ.values())
    if not steps:
        return None
    return 100.0 * sum(k * v for k, v in occ.items()) / (
        ctx["counters"]["lanes"] * steps)
