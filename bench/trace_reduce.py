"""Reduce a profiler trace to the numbers the per-layer metrics read.

``extract`` turns the profiler's ``.xplane.pb`` into plain lists: the
benchmark's own host spans (``bench.*``) and, for each device, its ops
(line ``XLA Ops``, named by their HLO instruction: ``%while.260 = (...)``
becomes ``while.260``) and its programs (line ``XLA Modules``), as
``(name, start_ns, end_ns)``.  ``reduce`` works on those lists only, so it is tested on a
small synthetic trace without a chip.

What it computes, inside the traced window (the host span ``bench.window``):

* busy time per device: the union of the intervals of its ops, leaving
  out the ops that only contain others (``while``, ``conditional``,
  ``call``: their bodies' ops are listed themselves, and a loop's span
  would cover the gaps between them); idle is the rest;
* idle gaps, each labelled by the innermost benchmark span that covers its
  middle on the host ("no span" where none does);
* op time by op name, over the same ops.
"""
from __future__ import annotations

import re
from collections import defaultdict

CONTAINER = re.compile(r"^(while|conditional|call)\b")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."
LINES = {"XLA Ops": "ops", "XLA Modules": "modules"}


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def extract(pd) -> dict:
    """Plain lists from a ``jax.profiler.ProfileData``."""
    host, devices = [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = LINES.get(line.name)
                if key:
                    name = (lambda n: n) if key == "modules" else op_name
                    dev[key] += [(name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                                 for e in line.events]
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return {"host": sorted(host, key=lambda s: s[1]),
            "devices": [devices[k] for k in sorted(devices)]}


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events, t0, t1):
    return [(n, max(s, t0), min(e, t1)) for n, s, e in events if e > t0 and s < t1]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def span_at(host, t: float) -> str:
    """The innermost benchmark span covering host time `t`."""
    best = None
    for name, s, e in host:
        if s > t:
            break
        if e >= t and (best is None or s >= best[1]):
            best = (name, s, e)
    return best[0][len(SPAN_PREFIX):] if best else "no span"


def reduce(ex: dict, n_devices: int) -> dict:
    host = sorted((s for s in ex["host"] if s[0].startswith(SPAN_PREFIX)),
                  key=lambda s: s[1])
    devs = ex["devices"][:n_devices]
    if not devs or not any(d["ops"] for d in devs):
        raise RuntimeError("the trace holds no device ops")
    win = [s for s in host if s[0] == SPAN_PREFIX + "window"]
    if win:
        t0, t1 = win[0][1], win[0][2]
    else:
        t0 = min(s for d in devs for _, s, _ in d["ops"])
        t1 = max(e for d in devs for _, _, e in d["ops"])
    per_dev = []
    idle_by_span: dict[str, float] = defaultdict(float)
    op_time: dict[str, float] = defaultdict(float)
    for d in devs:
        leaf = [(n, s, e) for n, s, e in _clip(d["ops"], t0, t1) if not CONTAINER.match(n)]
        busy = merge((s, e) for _, s, e in leaf)
        gaps = [(a[1], b[0]) for a, b in zip([(t0, t0)] + busy, busy + [(t1, t1)])
                if b[0] > a[1]]
        for s, e in gaps:
            idle_by_span[span_at(host, (s + e) / 2)] += (e - s) / len(devs)
        for n, s, e in leaf:
            op_time[n] += (e - s) / len(devs)
        per_dev.append({"busy_ns": _length(busy), "modules": _clip(d["modules"], t0, t1)})
    top = lambda d: [[k, v * 1e-9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": sum(p["busy_ns"] for p in per_dev) / len(per_dev) * 1e-9,
        "devices": per_dev,
        "op_s": {k: v * 1e-9 for k, v in op_time.items()},
        "host": [(n[len(SPAN_PREFIX):], s, e) for n, s, e in host
                 if t0 <= s <= t1],
        "breakdown": {"device_ops": top(op_time), "idle_gaps": top(idle_by_span)},
    }


def reduce_file(path: str, n_devices: int) -> dict:
    from jax.profiler import ProfileData

    return reduce(extract(ProfileData.from_file(path)), n_devices)
