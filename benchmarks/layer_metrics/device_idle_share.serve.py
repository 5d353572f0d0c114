"""Device: the share of the traced window in which no operation ran on
the fullest chip."""
from benchmarks import trace_reduce


def read(ctx):
    return trace_reduce.idle_share_percent(ctx.get("trace"))
