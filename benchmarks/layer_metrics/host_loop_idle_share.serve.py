"""Engine host loop: the share of the traced window in which the chip was
idle under the program's own host work, by the span that overlaps each
idle gap most (`trace_reduce`: idle_by_host_span over the `engine.*` and
`frontend.*` spans).  `device_idle_share.serve` less this is idle under
the wait for the device's tokens and under what is not the program's.

`engine.fetch` is the wait and not the loop: it wraps JAX's own
`np.asarray` span in the blocking read and ends a moment after it, so it
out-overlaps that span in every gap that starts inside both."""

PROGRAM = ("engine.", "frontend.")
WAIT = "engine.fetch"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or trace.get("window_s", 0) <= 0:
        return None
    by_span = trace.get("idle_by_host_span") or {}
    mine = [s for n, s in by_span.items()
            if n.startswith(PROGRAM) and n != WAIT]
    if not mine:
        return None
    return 100.0 * sum(mine) / trace["window_s"]
