"""Share of the traced window in which no operation ran on the device:
one minus the union of the device's operation intervals over the window."""


def read(ctx):
    s = ctx.summary
    return (1.0 - s["busy_s"] / s["window_s"]) * 100.0
