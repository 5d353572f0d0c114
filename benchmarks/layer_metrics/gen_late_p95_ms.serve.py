"""Load generator + edge: how late requests left against their schedule
(95th percentile over the window's requests).  A generator that runs late
offers less load than the cell states."""


def read(ctx):
    window = ctx.get("window")
    if not window or window.get("gen_late_p95_ms") is None:
        return None
    return window["gen_late_p95_ms"]
