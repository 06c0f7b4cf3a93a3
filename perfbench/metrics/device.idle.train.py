"""Device idle share of the training rounds, %: one minus the union of
the device's op intervals over the traced window."""


def read(ctx):
    dev = ctx["device"]
    if dev is None or dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
