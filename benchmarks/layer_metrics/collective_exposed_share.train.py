"""Device, cells across chips only: the share of the traced stretch in
which a collective ran on a chip with no compute beside it (the chip on
which that is longest)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or trace["window_s"] <= 0 or trace["n_devices"] < 2 \
            or trace["collective_s"] <= 0:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
