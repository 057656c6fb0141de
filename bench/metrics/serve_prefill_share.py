"""Share of the traced serving slice's device program time spent in chunked
prefill: the time of the launches of ``serve_prefill_chunk`` over that of
every named program's launches, counting launches that lie wholly inside
the slice, averaged over the chips (bench/program_trace.py)."""
from bench import program_trace


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    time = {p: sum(e - s for dev in program_trace.launches(tr, p) for s, e in dev)
            for p in program_trace.programs_in(tr) if p}
    if "serve_prefill_chunk" not in time or not sum(time.values()):
        return None
    return 100.0 * time["serve_prefill_chunk"] / sum(time.values())
