"""Lane occupancy of the prefill cells (see the decode twin): prompt chunks
and decode steps alike count as carried lane-steps."""
from harness.readers import lane_occupancy_pct as read  # noqa: F401
