"""device_idle.long_input: share of the traced window in which no operation
ran on the device (1 - busy / window), in the long-input cells. Moves
ttft_p90_ms."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
