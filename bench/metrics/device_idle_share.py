"""Share of the traced window in which no operation ran on the chip, in
percent, averaged over the cell's chips."""


def read(ctx):
    if ctx["trace_window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["trace_window_s"])
