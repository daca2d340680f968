"""Share of the traced device stage (the traffic's `device_stage`, one
stage of the block run on its own) in which no operation ran on the
device: 1 - (union of device-op intervals / the stage's window),
averaged over the chips."""


def read(ctx):
    r = ctx.stage
    if r is None or not r.window_ns:
        return None
    return 100.0 * (1.0 - r.busy_ns / r.window_ns)
