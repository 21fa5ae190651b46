"""Lane occupancy of the decode cells: the share of lane-steps that carried a
request, over the window's inner steps (`ContinuousBatcher.occupancy_ticks`,
the batcher's own counter, as deltas over the window)."""
from harness.readers import lane_occupancy_pct as read  # noqa: F401
