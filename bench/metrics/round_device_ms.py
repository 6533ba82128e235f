"""Device busy time per round of the traced window, milliseconds (per
chip, averaged over the cell's chips)."""


def read(ctx):
    if not ctx["rounds"]:
        return None
    return 1e3 * ctx["busy_s"] / ctx["rounds"]
