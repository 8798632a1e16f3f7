"""device_idle: 1 - busy / window on each chip, where busy is the union of
the chip's operation intervals in the traced window; the largest over the
cell's chips, in %."""


def read(ctx):
    red = ctx["trace"]
    if not red.chips or red.window_s <= 0:
        return None
    return 100.0 * max(1.0 - c.busy_s / red.window_s for c in red.chips)
