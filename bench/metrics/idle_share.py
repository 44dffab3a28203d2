"""Device: the share of the traced wave in which no operation ran on the
chip (1 - union of the device's op intervals / the wave's length)."""


def read(ctx):
    if not ctx.dev["window_ns"]:
        return None
    return 100.0 * (1.0 - ctx.dev["busy_ns"] / ctx.dev["window_ns"])
