"""Simulated cycles delivered per second of the measured window."""


def lane_cycles_per_s(ctx):
    """All simulated cycles of all finished jobs, summed over lanes, over
    all the wall time of the window (results on the host)."""
    return sum(j.lane_cycles for j in ctx["jobs"]) / ctx["window_s"]
