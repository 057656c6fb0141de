"""Share of the traced serving window in which no op runs on the device
(1 - union of op intervals / window), averaged over the cell's chips."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
