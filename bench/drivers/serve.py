"""Serving cells: the program's continuous-batching scheduler under an
open-loop request trace.

Set-up builds the model and its weights on the device from the seed, the
paged ``DecodeSpec`` and the ``ContinuousScheduler`` from the program's
``serve.common`` (``make_serve_spec``, ``ServeSetup``, ``make_scheduler``),
and warms every prefill bucket and the decode step through that scheduler.
The trace starts with a ramp: requests due before the window opens, served
as set-up, so that the window finds the scheduler as full as the cell's
rate keeps it.  The window submits each request when it is due and steps
the scheduler; every streamed token is kept, and stamped on the host when
``step()`` returns it.  Once the window has closed, the scheduler runs on
until every request due in the window has had its first token (up to
``drain_s``), and a seeded sample of the requests that finished, the longest
among them, is compared with the plain reference: for each streamed token,
the gap by which the reference's logit of that token lies below its best.
A stream still running when the window closes is not compared and is not
missing.
"""
from __future__ import annotations

import gc
import importlib
import math
import time

import numpy as np

from bench import harness, traffic as traffic_gen
from bench.drivers.train import model_dims
from bench.harness import span


def build(config: dict, traffic: dict, seed: int, devices=None):
    import jax
    from jax.sharding import NamedSharding
    from repro.core.qsdp import MeshSpec, QSDPConfig
    from repro.launch.mesh import make_mesh
    from repro.models.config import ModelConfig
    from repro.models.transformer import Model
    from repro.serve.common import ServeSetup, make_scheduler, make_serve_spec
    from repro.serve.engine import ServeEngine

    sv = config["serve"]
    mcfg = ModelConfig(name=config["name"], arch_type="dense", tie_embeddings=True,
                       **model_dims(config))
    mesh = make_mesh((1, 1), ("data", "model"), devices=devices)
    ms = MeshSpec(axes=("data", "model"), shape=(1, 1))
    q = sv["qsdp"]
    model = Model(mcfg, ms, QSDPConfig(weight_bits=q["weight_bits"],
                                       bucket_size=q["bucket_size"],
                                       min_quant_size=q["min_quant_size"]))
    shard = {k: NamedSharding(mesh, p) for k, p in model.param_pspecs().items()}
    params = jax.jit(model.init_params, out_shardings=shard)(
        jax.random.PRNGKey(harness.seed32(seed)))
    spec = make_serve_spec(mcfg, ms, sv["slots"], traffic["prompt_len"]["max"],
                           traffic["output_len"]["max"],
                           kv_block_size=sv["kv_block_size"],
                           kv_pool_blocks=sv["kv_pool_blocks"])
    setup = ServeSetup(cfg=mcfg, model=model, params=params, mesh=mesh, ms=ms,
                       spec=spec, engine=ServeEngine(model, mesh, spec))
    sched = make_scheduler(setup, gather_key=jax.random.PRNGKey(harness.seed32(seed) + 1),
                           prefill_chunk=sv["prefill_chunk"],
                           prefill_buckets=sv["prefill_buckets"])
    return setup, sched


def warm(sched, vocab: int) -> None:
    """One request per prefill bucket, each run to its end: every program
    the window launches is compiled (or loaded) here."""
    from repro.serve.scheduler import Request

    rng = np.random.default_rng(0)
    for i, b in enumerate(sched.buckets):
        sched.submit(Request(rid=f"warm{i}", prompt=rng.integers(0, vocab, b).tolist(),
                             max_new_tokens=2))
        sched.run()


def nearest_rank(values, q: float) -> float:
    """The q-quantile by nearest rank (a missing value is +inf)."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def open_loop(sched, reqs, seconds: float, ramp_s: float, drain_s: float, prof=None,
              trace_seconds: float = 0.0) -> dict:
    """Submit each request when it is due, step the scheduler, and stamp
    every streamed token.

    The requests due before 0 are the ramp, served for `ramp_s` before the
    window opens (part of set-up); the window opens at the first check
    after the ramp and lasts `seconds`.  Once it has closed, the scheduler
    is stepped on until every request due in the window has had its first
    token (up to `drain_s`): a first token that comes late is late, and its
    TTFT counts the wait; one that never comes counts as +inf.  Tokens of
    requests that are not in `reqs` (left running by an earlier call) are
    not recorded.

    TTFT counts from when a request was due, over the requests due in the
    window; the gaps between tokens are those whose later token came inside
    the window, of every request; tokens per second are the tokens streamed
    inside the window, of every request, over the window.  `prof` traces
    `trace_seconds` from the window's middle."""
    from repro.serve.scheduler import Request

    times: dict[str, list[float]] = {r.rid: [] for r in reqs}
    toks: dict[str, list[int]] = {r.rid: [] for r in reqs}
    done: set[str] = set()
    window = [r for r in reqs if r.due_s >= 0]
    late, k = 0.0, 0
    kv_peak = 0

    def record(events, t):
        for ev in events:
            if ev.rid in times:
                times[ev.rid].append(t)
                toks[ev.rid].append(ev.token)
                if ev.done:
                    done.add(ev.rid)

    def serve_until(t_zero, t_stop, in_window):
        nonlocal late, k, kv_peak
        while True:
            now = time.perf_counter()
            if prof is not None and in_window:
                if now >= t_zero + trace_from + trace_seconds:
                    prof.stop()
                elif now >= t_zero + trace_from:
                    prof.start()
            while k < len(reqs) and t_zero + reqs[k].due_s <= now:
                r = reqs[k]
                with span("submit"):
                    sched.submit(Request(rid=r.rid, prompt=r.prompt.tolist(),
                                         max_new_tokens=r.max_new_tokens))
                late = max(late, now - (t_zero + r.due_s))
                k += 1
            if sched.queue or sched.n_active():
                with span("sched_step"):
                    events = sched.step()
                record(events, time.perf_counter())
                if in_window:
                    kv_peak = max(kv_peak, sched.pool.blocks_in_use)
            elif now < t_stop:
                nxt = t_zero + reqs[k].due_s if k < len(reqs) else t_stop
                with span("wait_arrival"):
                    time.sleep(max(0.0, min(nxt, t_stop) - now))
            if now >= t_stop:
                return

    trace_from = max(0.0, (seconds - trace_seconds) / 2)
    t_ramp = time.perf_counter() + ramp_s
    serve_until(t_ramp, t_ramp, False)  # the ramp: every request due before 0
    t0 = time.perf_counter()
    base, queued_at_open = sched.stats(), len(sched.queue)
    t_end = t0 + seconds
    try:
        serve_until(t0, t_end, True)
    finally:
        if prof is not None:
            prof.stop()
    stats = sched.stats()
    queued_at_close = len(sched.queue)
    t_drain = time.perf_counter() + drain_s
    while (any(not times[r.rid] for r in window) and time.perf_counter() < t_drain
           and (sched.queue or sched.n_active())):
        record(sched.step(), time.perf_counter())

    ttft = [(times[r.rid][0] - (t0 + r.due_s)) * 1e3 if times[r.rid] else math.inf
            for r in window]
    itl = [(b - a) * 1e3 for r in reqs for a, b in zip(times[r.rid], times[r.rid][1:])
           if t0 < b <= t_end]
    in_window = sum(1 for r in reqs for t in times[r.rid] if t0 <= t <= t_end)
    lane_steps = stats["lane_steps"] - base["lane_steps"]
    steps = stats["decode_steps"] - base["decode_steps"]
    return {
        "e2e": {"serve_tokens_per_s": in_window / seconds,
                "serve_ttft_p50_ms": nearest_rank(ttft, 0.5),
                "serve_itl_p95_ms": nearest_rank(itl, 0.95) if itl else math.inf},
        "ttft_ms": {"requests": len(ttft), **{f"p{q}": nearest_rank(ttft, q / 100)
                                              for q in (50, 90, 95, 100)}},
        "t_open": t0, "done": done, "toks": toks, "late_s": late,
        "missing": sum(1 for r in reqs if not times[r.rid]),
        "window_requests": len(window),
        "finished_in_window": sum(1 for r in reqs if r.rid in done and times[r.rid][-1] <= t_end),
        "first_tokens_in_window": sum(1 for r in window if times[r.rid]
                                      and times[r.rid][0] <= t_end),
        "queued_at_open": queued_at_open, "queued_at_close": queued_at_close,
        "mean_occupancy": lane_steps / max(1, steps), "decode_steps": steps,
        "prefill_chunks": stats["prefill_chunks"] - base["prefill_chunks"],
        "kv_blocks_peak": kv_peak,
        "offered_tokens_per_s": sum(r.max_new_tokens for r in window) / seconds,
    }


def run(spec: dict, seed: int, seconds: float, trace: bool, t_start: float,
        device: dict) -> dict:
    import jax

    config, tr, cell = spec["config"], spec["traffic"], spec["cell"]
    vocab = config["vocab_size"]
    devices = jax.devices()[:1]
    with span("setup.build"):
        setup, sched = build(config, tr, seed, devices)
        jax.block_until_ready(setup.params)
    t_state = time.perf_counter()
    with span("setup.warm"):
        warm(sched, vocab)
    t_warm = time.perf_counter()
    reqs = traffic_gen.request_trace(tr, seed, seconds, vocab)

    prof = harness.Profile(spec["name"], trace)
    w = open_loop(sched, reqs, seconds, tr.get("ramp_s", 0), cell["drain_s"], prof,
                  cell["trace_seconds"])
    setup_s = w["t_open"] - t_start  # the ramp is set-up: it fills the scheduler
    peak = harness.memory_peak_bytes(devices)
    e2e = dict(w["e2e"], setup_s=setup_s)
    done, toks = w["done"], w["toks"]
    served = {r.rid: (r.prompt, toks[r.rid]) for r in reqs if r.rid in done}
    pool_blocks = sched.pool.n_blocks
    del setup, sched
    gc.collect()

    checks = compare(spec, seed, served)
    checks["missing_answers"] = {"value": w["missing"], "limit": 0}
    ctx = {"e2e": e2e, "spec": spec, "chips": 1, "memory_peak_bytes": peak,
           "peaks": harness.peaks(device["kind"]),
           "counters": {"mean_occupancy": w["mean_occupancy"],
                        "slots": config["serve"]["slots"]}}
    extra = {"setup_split": {"state_s": t_state - t_start, "warm_s": t_warm - t_state,
                             "ramp_s": w["t_open"] - t_warm},
             "generator_late_ms": w["late_s"] * 1e3, "ttft_ms": w["ttft_ms"],
             "window": {k: w[k] for k in ("window_requests", "first_tokens_in_window",
                                          "finished_in_window", "queued_at_open",
                                          "queued_at_close", "mean_occupancy",
                                          "decode_steps", "prefill_chunks")},
             "kv_blocks_peak": w["kv_blocks_peak"], "kv_blocks": pool_blocks,
             "finished_compared_from": len(served)}
    return harness.result_line(spec, checks, len(reqs), w["missing"], device, prof,
                               ctx, extra)


def sample(served: dict, seed: int, tokens: int) -> list:
    """A seeded sample of finished requests, the one with the most served
    tokens first, until `tokens` served tokens are in it."""
    rids = sorted(served)
    if not rids:
        return []
    longest = max(rids, key=lambda r: (len(served[r][1]), r))
    rest = [r for r in rids if r != longest]
    order = [longest] + [rest[i] for i in
                         np.random.default_rng(harness.seed32(seed)).permutation(len(rest))]
    out, n = [], 0
    for r in order:
        out.append(served[r])
        n += len(served[r][1])
        if n >= tokens:
            break
    return out


def reference_gaps(spec: dict, seed: int, picked: list, **kw) -> list:
    import jax

    config = spec["config"]
    ref_mod = importlib.import_module(f"bench.configs.{config['reference']}")
    tr = spec["traffic"]
    length = tr["prompt_len"]["max"] + tr["output_len"]["max"]
    return ref_mod.served_gaps(model_dims(config), jax.random.PRNGKey(harness.seed32(seed)),
                               picked, length, **kw)


def compare(spec: dict, seed: int, served: dict) -> dict:
    cell = spec["cell"]
    picked = sample(served, seed, cell["check_tokens"])
    gap = max(reference_gaps(spec, seed, picked)) if picked else math.inf
    return {"served_logit_gap": {"value": gap,
                                 "limit": cell["limits"]["served_logit_gap"]}}
