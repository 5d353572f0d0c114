"""Engine host loop: the host's seconds a step outside what
`engine_step_ms.serve` times (operand upload to fetched tokens), during
which the chip has nothing to run: the rest of `DecodeEngine.step`'s wall
(admission, page growth, batch assembly, token delivery, the step's tail)
plus the frontend's wall from a step's return to the next step's call
(`decode_stats`: (host_in_step_s + between_steps_s) / steps).  The two
metrics add up to the engine's period a step while it has a batch."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("steps") or "host_in_step_s" not in c:
        return None
    return 1e3 * (c["host_in_step_s"] + c["between_steps_s"]) / c["steps"]
