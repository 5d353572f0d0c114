"""Scheduler: the share of the engine's slots that held a sequence, mean
over the window's steps (`decode_stats`: occupancy_sum / steps)."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("steps"):
        return None
    return 100.0 * c["occupancy_sum"] / c["steps"]
