"""Mean share of the scheduler's slots that hold a decoding request, per
decode step of the window (the scheduler's lane-step and decode-step
counters)."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("slots"):
        return None
    return 100.0 * c["mean_occupancy"] / c["slots"]
