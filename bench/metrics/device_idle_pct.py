"""Share of the traced steady window in which no operation runs on the
device, averaged over the cell's chips (1 - union of device-operation
intervals / window)."""


def read(ctx):
    red = ctx["trace"]
    if red.window_s <= 0 or red.busy_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
