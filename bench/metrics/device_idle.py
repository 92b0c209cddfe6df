"""device_idle: 1 - the union of the device's operation intervals over the
traced window, in percent (profiler trace)."""


def read(ctx):
    red = ctx["trace"]
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
