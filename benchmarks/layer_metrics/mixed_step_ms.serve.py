"""Engine: the seconds of a step that carries prompt chunks beside the
decode rows, dispatch to fetched tokens, mean over the window's mixed
steps (`decode_stats`: mixed_time_s / mixed_steps); `engine_step_ms.serve`
blends them with the plain decode steps."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("mixed_steps") or "mixed_time_s" not in c:
        return None
    return 1e3 * c["mixed_time_s"] / c["mixed_steps"]
