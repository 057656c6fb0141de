"""Mean device time of one pooled decode launch, from the trace's program
(module) events: within each scheduler step (host span
``bench.sched_step``), the decode is the last program the step launched;
the decode program is the one that most often comes last, and every launch
of it in the traced slice is averaged."""
from collections import Counter


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    mods = sorted(tr["devices"][0]["modules"], key=lambda m: m[1])
    steps = [(s, e) for n, s, e in tr["host"] if n == "sched_step"]
    last = Counter()
    j = 0
    for s, e in steps:
        while j < len(mods) and mods[j][1] < s:
            j += 1
        k, name = j, None
        while k < len(mods) and mods[k][1] < e:
            name = mods[k][0]
            k += 1
        if name is not None:
            last[name] += 1
    if not last:
        return None
    decode = last.most_common(1)[0][0]
    durs = [e - s for n, s, e in mods if n == decode]
    return sum(durs) / len(durs) * 1e-6
