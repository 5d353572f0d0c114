"""Scheduler: a request's wait from its enqueue in the engine to its first
admission (a slot and its prompt's pages), mean over the window's
admissions (`decode_stats`: queue_wait_s / admissions)."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("admissions"):
        return None
    return 1e3 * c["queue_wait_s"] / c["admissions"]
