"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    tr = ctx["trace"]
    return (1.0 - tr.busy_s / tr.window_s) * 100.0
