"""Share of the traced window in which the device ran no operation:
1 - (union of the device's operation intervals) / window, averaged over
the chips, in percent."""


def read(ctx, metric):
    red = ctx.observed["trace"]
    if not red.n_devices:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
