"""Device: share of the traced window in which no operation ran."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.chips or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
