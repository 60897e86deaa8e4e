"""Share of the traced aggregate in which no operation ran on the device:
1 - busy union / window, averaged over the chips."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - tr.busy_s() / (tr.window_ns / 1e9))
