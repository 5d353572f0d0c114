"""Scheduler + engine host loop: the engine's own seconds a step
(`decode_stats`: a host clock around a step that ends in a blocking fetch),
over the steps of the window."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("steps"):
        return None
    return 1e3 * c["decode_time_s"] / c["steps"]
