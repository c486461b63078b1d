"""device_idle.resident_decode: share of the traced window in which no
operation ran on the device (1 - busy / window), in the resident-decode
cells. Moves tpot_ms."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
