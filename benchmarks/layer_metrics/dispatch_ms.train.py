"""Entry point: host milliseconds in the call that enqueues one train step
(no fence), the mean over the window's steps."""


def read(ctx):
    times = ctx.get("dispatch_seconds")
    if not times:
        return None
    return 1e3 * sum(times) / len(times)
