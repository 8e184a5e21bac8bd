"""Share of the traced catch-up window in which no operation ran on the
device (1 - busy / window, busy the union of op intervals)."""


def read(ctx):
    red = ctx["trace"]
    if not red:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
