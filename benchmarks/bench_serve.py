"""Benchmark: continuous-batching serving under a synthetic arrival trace.

The serving analogue of bench_step.py.  On an emulated (2 data x 4 model)
8-device CPU mesh, a fixed pool of decode slots drains a DETERMINISTIC
synthetic request trace — seeded Poisson arrival gaps, mixed prompt and
generation lengths — through serve.ContinuousScheduler, for each wire
policy:

  baseline-fsdp        f32 weight gathers every decode step
  qsdp                 W8 quantized gathers (paper Section 5 wire format)
  qsdp-rowquant-wire   W8 gathers consumed in wire-code form by the fused
                       rowquant matmul (dense-MLP weights never dequantized
                       to HBM)
  qsdp-spec            self-speculative decode: a 4-bit rowquant
                       re-quantization of the SAME weights drafts 4
                       tokens/slot/step, the serving-precision model
                       verifies them in one pooled launch — committed
                       tokens are asserted bit-equal to the qsdp row,
                       with accepted_per_launch > 1 and
                       launches_per_token < 1 as CI tripwires

Decode is FSDP-style — every step re-gathers the sharded weights — so step
latency is collective-bound and the gather wire bytes per decode step are
the headline column: QSDP ships ~bits/32 of the baseline's bytes for the
same trace, slots, and per-request token counts.  (Baseline decodes f32
weights while the quantized variants decode quantized ones, so their
greedy TOKENS may differ; qsdp and qsdp-rowquant-wire consume the same
quantized weights and are asserted token-identical.)

A second LONG-PROMPT trace (many distinct prompt lengths, prompts several
times the chunk size) replays through the qsdp wire policy under both
admission paths:

  qsdp-longprompt      blocking whole-prompt admission (one jit retrace
                       per distinct prompt length; every admission stalls
                       live decode slots for the full prompt)
  qsdp-chunked         chunked, length-bucketed prefill (--prefill-chunk):
                       at most one chunk rides each scheduler step, jit
                       cache bounded at n_buckets traces

and the run ASSERTS the bounded-retrace guarantee (a regression back to
per-length retraces fails CI), the chunked slot-isolation invariant
(every chunked request's greedy tokens bit-match its solo batch-of-1 run
with the SAME chunk decomposition, generate(prefill_chunk=C,
fold_step_keys=False) — chunked and whole-prompt prefill are distinct
float paths, so each admission path is held to ITS solo reference), and
the bounded per-launch stall (max_prefill_launch_tokens <= the padded
chunk, vs the full prompt under blocking).

Per variant this reports
  * tokens/s over the timed replay (compile excluded via a warmup drain
    that covers every distinct prompt length / chunk bucket in the trace),
  * per-request latency (submit -> last token) p50/p95, in decode steps
    and in wall seconds, plus p95 time-to-first-token,
  * mean slot occupancy of the pool,
  * analytic per-decode-step weight-gather wire bytes per device,
  * prefill trace/launch counts and the per-launch stall bound,

and writes everything to BENCH_serve.json (uploaded as a CI artifact next
to BENCH_step.json).

Run:  PYTHONPATH=src python benchmarks/bench_serve.py --smoke
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    # 8 emulated devices when the backend is the CPU; appended, so a
    # caller's own XLA_FLAGS survive, and ignored by a TPU backend.
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
# ^ MUST precede every other import: jax locks the device count on first init.

import argparse
import json
import time

try:
    from . import bench_schema
except ImportError:  # run as a script: sys.path[0] is benchmarks/
    import bench_schema

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.qsdp import QSDPConfig
from repro.models.config import ModelConfig
from repro.serve import ContinuousScheduler, Request, build_serve_setup


def _round_floats(obj, ndigits=4):
    """Round every float in a JSON tree to `ndigits` decimals so the
    emitted artifact is stable to read and diff (no
    4.6499999999999995-style repr noise from ratio arithmetic)."""
    if isinstance(obj, float):
        return round(obj, ndigits)
    if isinstance(obj, dict):
        return {k: _round_floats(v, ndigits) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_round_floats(v, ndigits) for v in obj]
    return obj


def variants():
    return {
        "baseline-fsdp": dict(qsdp=QSDPConfig.baseline(), rowquant=False),
        "qsdp": dict(qsdp=QSDPConfig(min_quant_size=256), rowquant=False),
        "qsdp-rowquant-wire": dict(qsdp=QSDPConfig(min_quant_size=256),
                                   rowquant=True),
    }


def make_trace(rng, n_requests, arrival_rate, prompt_lens, gen_lens, vocab,
               cycle_lens=False):
    """Deterministic synthetic load: (arrival_step, Request) pairs.  Arrival
    gaps are Poisson (exponential inter-arrival, rounded to decode steps);
    prompt/gen lengths cycle through mixed buckets.  cycle_lens=True walks
    prompt_lens round-robin instead of sampling, guaranteeing every
    distinct length appears (the long-prompt retrace assertions need
    that)."""
    trace = []
    step = 0
    for i in range(n_requests):
        step += int(rng.exponential(1.0 / arrival_rate))
        plen = int(prompt_lens[i % len(prompt_lens)] if cycle_lens
                   else rng.choice(prompt_lens))
        gen = int(rng.choice(gen_lens))
        trace.append((step, Request(
            rid=f"req{i:03d}", prompt=rng.integers(0, vocab, size=plen).tolist(),
            max_new_tokens=gen, seed=i)))
    return trace


def make_prefix_trace(rng, n_requests, arrival_rate, sys_len, tail_lens,
                      gen_lens, vocab):
    """Repeated-system-prompt load: every request's prompt = one fixed
    `sys_len`-token system prefix + a distinct random tail — the dominant
    real traffic shape, and the one the paged pool's prefix sharing is
    for."""
    system = rng.integers(0, vocab, size=sys_len).tolist()
    trace = []
    step = 0
    for i in range(n_requests):
        step += int(rng.exponential(1.0 / arrival_rate))
        tail = rng.integers(0, vocab,
                            size=int(tail_lens[i % len(tail_lens)])).tolist()
        trace.append((step, Request(
            rid=f"sys{i:03d}", prompt=system + tail,
            max_new_tokens=int(rng.choice(gen_lens)), seed=i)))
    return trace


def replay(sched, trace, max_steps=100_000):
    """Drive the scheduler through the arrival trace: requests are submitted
    when the scheduler's decode-step clock (relative to replay start)
    reaches their arrival step; an idle pool fast-forwards to the next
    arrival."""
    pending = list(trace)
    start = sched.step_count
    skipped = 0  # idle steps fast-forwarded on the virtual arrival clock
    t0 = time.perf_counter()
    steps = 0
    while pending or sched.queue or sched.n_active():
        clock = sched.step_count - start + skipped
        while pending and pending[0][0] <= clock:
            sched.submit(pending.pop(0)[1])
        if pending and not (sched.queue or sched.n_active()):
            # idle server: fast-forward the virtual clock to the next
            # arrival (later arrivals keep their relative gaps)
            skipped += pending[0][0] - clock
            continue
        sched.step()
        steps += 1
        assert steps < max_steps, "trace replay did not converge"
    return time.perf_counter() - t0


def bench_variant(name, qsdp, rowquant, mcfg, trace, slots,
                  prefill_chunk=0, prefill_buckets=4, kv_block_size=0,
                  kv_quant_bits=0, kv_quant_horizon=0, kv_prefix_share=True,
                  draft_bits=0, draft_depth=0):
    prompt_lens = sorted({len(r.prompt) for _, r in trace})
    gen0 = trace[0][1].max_new_tokens
    setup = build_serve_setup(
        mcfg, data_par=2, model_par=4, qsdp=qsdp, batch=slots,
        prompt_len=max(prompt_lens),
        gen=max(r.max_new_tokens for _, r in trace), rowquant_mlp=rowquant,
        kv_block_size=kv_block_size,
        draft_bits=draft_bits, draft_depth=draft_depth)
    sched = ContinuousScheduler(setup.model, setup.mesh, setup.spec,
                                setup.params,
                                gather_key=jax.random.PRNGKey(42),
                                prefill_chunk=prefill_chunk,
                                prefill_buckets=prefill_buckets,
                                kv_quant_bits=kv_quant_bits,
                                kv_quant_horizon=kv_quant_horizon,
                                kv_prefix_share=kv_prefix_share)

    # warmup: compile decode + one prefill per distinct prompt length
    # (blocking) / per chunk bucket (chunked: one prompt of each bucket
    # length, run one at a time so every bucket's launch compiles before
    # the timed replay); speculative variants warm at full generation
    # length so the deeper draft/verify launch shapes compile too
    warm_gen = gen0 if setup.spec.speculative else min(gen0, 2)
    t0 = time.perf_counter()
    if prefill_chunk:
        for j, blen in enumerate(sched.buckets):
            sched.submit(Request(rid=f"warm{j}",
                                 prompt=list(range(1, blen + 1)),
                                 max_new_tokens=warm_gen, seed=0))
            sched.run()
    else:
        for j, plen in enumerate(prompt_lens):
            sched.submit(Request(rid=f"warm{j}",
                                 prompt=list(range(1, plen + 1)),
                                 max_new_tokens=warm_gen, seed=0))
        sched.run()
    compile_s = time.perf_counter() - t0

    # timed replay (snapshot counters so warmup is excluded)
    base = sched.stats()
    wall_s = replay(sched, trace)
    st = sched.stats()
    done = {r.rid: sched.finished[r.rid] for _, r in trace}
    lat_steps = [c.finish_step - c.submit_step for c in done.values()]
    lat_s = [c.finish_time - c.submit_time for c in done.values()]
    ttft_s = [c.first_token_time - c.submit_time for c in done.values()]
    tokens = st["tokens_generated"] - base["tokens_generated"]
    steps = st["decode_steps"] - base["decode_steps"]
    occ = ((st["lane_steps"] - base["lane_steps"]) / max(steps, 1))
    # launch accounting over the timed replay only (warmup deltas out),
    # normalized per lane so it is batch-composition independent: 1.0 =
    # one serving-precision lane-step per decoded token (non-speculative
    # decode by construction), < 1.0 = speculation committing > 1
    dec_tokens = max(1, tokens - (st["prefills"] - base["prefills"]))
    lpt = (st["lane_steps"] - base["lane_steps"]) / dec_tokens
    spec_ls = st["spec_lane_steps"] - base["spec_lane_steps"]
    apl = ((st["spec_tokens"] - base["spec_tokens"]) / spec_ls
           if spec_ls else 0.0)
    draft_oh = (st["draft_lane_steps"] - base["draft_lane_steps"]) / dec_tokens
    return {
        "compile_s": round(compile_s, 1),
        "wall_s": round(wall_s, 2),
        "tokens": int(tokens),
        "tokens_per_s": round(tokens / wall_s, 2),
        "decode_steps": int(steps),
        "step_ms_mean": round(1e3 * wall_s / max(steps, 1), 2),
        "latency_steps_p50": float(np.percentile(lat_steps, 50)),
        "latency_steps_p95": float(np.percentile(lat_steps, 95)),
        "latency_s_p50": round(float(np.percentile(lat_s, 50)), 3),
        "latency_s_p95": round(float(np.percentile(lat_s, 95)), 3),
        "ttft_s_p95": round(float(np.percentile(ttft_s, 95)), 3),
        "mean_occupancy": round(occ, 2),
        "slots": slots,
        "launches_per_token": round(lpt, 4),
        "accepted_per_launch": round(apl, 4),
        "draft_overhead": round(draft_oh, 4),
        "draft_launches": int(st["draft_launches"] - base["draft_launches"]),
        "verify_launches": int(st["verify_launches"]
                               - base["verify_launches"]),
        "gather_bytes_per_decode_step": int(setup.decode_gather_bytes()),
        "prefill_chunk": prefill_chunk,
        "prefill_traces": int(st["prefill_traces"]),
        "prefill_launches": int((st["prefill_chunks"] or st["prefills"])
                                - (base["prefill_chunks"] or base["prefills"])),
        "max_prefill_launch_tokens": int(st["max_prefill_launch_tokens"]),
        # paged-pool columns (0 / 0.0 under ring serving)
        "blocks_in_use": int(st.get("blocks_in_use", 0)),
        "blocks_cached": int(st.get("blocks_cached", 0)),
        "prefix_hit_rate": round(float(st.get("prefix_hit_rate", 0.0)), 3),
        "effective_capacity": float(st.get("effective_capacity", 0.0)),
        "cold_blocks": int(st.get("cold_blocks", 0)),
        "cold_bytes": int(st.get("cold_bytes", 0)),
        "hot_block_bytes": int(st.get("hot_block_bytes", 0)),
        "cold_compression": round(float(st.get("cold_compression", 1.0)), 2),
        "cow_forks": int(st.get("cow_forks", 0)),
        "demotions": int(st.get("demotions", 0)),
        "rehydrations": int(st.get("rehydrations", 0)),
    }, {rid: c.tokens.tolist() for rid, c in done.items()}, sched


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config for CI (fast compile, short trace)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--arrival-rate", type=float, default=1.5,
                    help="mean arrivals per decode step")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="chunk size for the qsdp-chunked long-prompt row")
    ap.add_argument("--prefill-buckets", type=int, default=4)
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)

    if args.smoke:
        dims = dict(n_layers=2, d_model=128, d_ff=256)
        n_requests = args.requests or 8
        prompt_lens, gen_lens = (8, 12), (3, 4, 6)
        # long-prompt trace: >= 8 distinct lengths, prompts several chunks
        # long — the retrace + head-of-line-blocking regime
        long_lens, long_n = tuple(range(9, 17)), 8
        # repeated-system-prompt trace (paged prefix sharing)
        sys_len, tail_lens, sys_n = 16, (3, 5, 7, 9, 11, 13), 8
    else:
        dims = dict(n_layers=4, d_model=256, d_ff=512)
        n_requests = args.requests or 24
        prompt_lens, gen_lens = (16, 32, 48), (8, 16, 24)
        long_lens, long_n = tuple(range(33, 64, 3)), 16
        sys_len, tail_lens, sys_n = 32, tuple(range(5, 40, 5)), 12
    kv_bs = 8  # paged block size (divides sys_len and the chunk size)

    mcfg = ModelConfig(name="bench-serve", arch_type="dense",
                       n_layers=dims["n_layers"], d_model=dims["d_model"],
                       vocab_size=512, n_heads=8, n_kv_heads=4,
                       head_dim=dims["d_model"] // 8, d_ff=dims["d_ff"])
    rng = np.random.default_rng(0)
    trace = make_trace(rng, n_requests, args.arrival_rate, prompt_lens,
                       gen_lens, mcfg.vocab_size)

    out = {"config": {**dims, "mesh": "2x4", "slots": args.slots,
                      "requests": n_requests, "arrival_rate": args.arrival_rate,
                      "prompt_lens": list(prompt_lens),
                      "gen_lens": list(gen_lens),
                      "long_prompt_lens": list(long_lens),
                      "prefill_chunk": args.prefill_chunk,
                      "prefill_buckets": args.prefill_buckets,
                      "kv_block_size": kv_bs, "sys_prompt_len": sys_len,
                      "smoke": bool(args.smoke)},
           "variants": {}}
    outputs = {}

    def show(name, r):
        spec = (f"  acc/launch {r['accepted_per_launch']:.2f}  "
                f"draft-oh {r['draft_overhead']:.2f}"
                if r["verify_launches"] else "")
        print(f"{name:20s} {r['tokens_per_s']:8.1f} tok/s  "
              f"step {r['step_ms_mean']:7.1f}ms  "
              f"lat p50/p95 {r['latency_steps_p50']:.0f}/"
              f"{r['latency_steps_p95']:.0f} steps  "
              f"ttft p95 {r['ttft_s_p95']:.3f}s  "
              f"occ {r['mean_occupancy']:.2f}/{r['slots']}  "
              f"l/tok {r['launches_per_token']:.2f}  "
              f"pf {r['prefill_traces']} traces/"
              f"{r['max_prefill_launch_tokens']} tok-stall  "
              f"gather {r['gather_bytes_per_decode_step'] / 2**20:.2f} "
              f"MiB/step{spec}")

    for name, v in variants().items():
        r, toks, _ = bench_variant(name, v["qsdp"], v["rowquant"], mcfg,
                                   trace, args.slots)
        out["variants"][name] = r
        outputs[name] = toks
        show(name, r)

    # self-speculative decoding over the SAME trace and qsdp wire policy:
    # the 4-bit rowquant re-quantization of the serving weights drafts 4
    # tokens per slot per step, the serving-precision model verifies them
    # in one pooled launch.  CI tripwires: committed tokens bit-equal the
    # non-speculative qsdp row (speculation is a pure launch-count
    # optimization), > 1 token committed per verify launch, and < 1
    # serving-precision lane-step per decoded token.
    r, toks, _ = bench_variant("qsdp-spec", QSDPConfig(min_quant_size=256),
                               False, mcfg, trace, args.slots,
                               draft_bits=4, draft_depth=4)
    out["variants"]["qsdp-spec"] = r
    outputs["qsdp-spec"] = toks
    show("qsdp-spec", r)
    assert outputs["qsdp-spec"] == outputs["qsdp"], \
        "speculative decode changed a request's committed tokens"
    assert r["accepted_per_launch"] > 1, r["accepted_per_launch"]
    assert r["launches_per_token"] < 1, r["launches_per_token"]

    # long-prompt trace: blocking vs chunked admission over the SAME qsdp
    # wire policy (chunked is the fix for per-length retraces + prefill
    # head-of-line blocking, so this is where its columns mean something)
    long_trace = make_trace(np.random.default_rng(1), long_n,
                            args.arrival_rate, long_lens, gen_lens,
                            mcfg.vocab_size, cycle_lens=True)
    for name, chunk in (("qsdp-longprompt", 0),
                        ("qsdp-chunked", args.prefill_chunk)):
        r, toks, _ = bench_variant(name, QSDPConfig(min_quant_size=256), False,
                                   mcfg, long_trace, args.slots,
                                   prefill_chunk=chunk,
                                   prefill_buckets=args.prefill_buckets)
        out["variants"][name] = r
        outputs[name] = toks
        show(name, r)

    # equal-tokens guarantee: every variant decoded the same trace greedily;
    # the quantized variants may *sample different tokens* than f32 baseline
    # (different weights), but qsdp vs qsdp-rowquant-wire consume the SAME
    # quantized weights and must agree token-for-token.
    assert outputs["qsdp"] == outputs["qsdp-rowquant-wire"], \
        "rowquant-wire decode diverged from the dense-dequant qsdp decode"
    b = out["variants"]["baseline-fsdp"]["gather_bytes_per_decode_step"]
    q = out["variants"]["qsdp"]["gather_bytes_per_decode_step"]
    rq = out["variants"]["qsdp-rowquant-wire"]["gather_bytes_per_decode_step"]
    assert q < b and rq < b, (q, rq, b)

    # chunked-admission contract on the long-prompt trace (CI tripwires):
    # slot isolation — every chunked request's greedy tokens bit-match its
    # solo batch-of-1 run with the SAME chunk decomposition; jit cache
    # bounded by the bucket count even though the trace has len(long_lens)
    # distinct prompt lengths (blocking compiles one trace per length — a
    # regression back to that fails here); and a live slot never stalls
    # behind more than one padded chunk of prefill.
    blk = out["variants"]["qsdp-longprompt"]
    chk = out["variants"]["qsdp-chunked"]
    solo_setup = build_serve_setup(
        mcfg, data_par=2, model_par=4, qsdp=QSDPConfig(min_quant_size=256),
        batch=1, prompt_len=max(long_lens),
        gen=max(r.max_new_tokens for _, r in long_trace),
        batch_sharded=False)
    for _, req in long_trace:
        ref = np.asarray(jax.device_get(solo_setup.engine.generate(
            solo_setup.params,
            {"tokens": jnp.asarray(np.asarray(req.prompt, np.int32)[None])},
            {"tokens": P(None)}, n_tokens=req.max_new_tokens,
            key=jax.random.PRNGKey(42), fold_step_keys=False,
            prefill_chunk=args.prefill_chunk,
            prefill_buckets=args.prefill_buckets)))[0].tolist()
        assert outputs["qsdp-chunked"][req.rid] == ref, \
            f"chunked {req.rid} diverged from its solo chunked run"
    assert chk["prefill_traces"] <= args.prefill_buckets, \
        (chk["prefill_traces"], args.prefill_buckets)
    assert blk["prefill_traces"] == len(long_lens), blk["prefill_traces"]
    if args.prefill_buckets < len(long_lens):
        # the headline guarantee — fewer compiled prefill shapes than
        # distinct prompt lengths (vacuous if the CLI raised the bucket
        # count past the trace's length diversity)
        assert chk["prefill_traces"] < blk["prefill_traces"], (chk, blk)
    chunk_top = min(args.prefill_chunk, solo_setup.spec.cache_len)
    assert chk["max_prefill_launch_tokens"] <= chunk_top, (chk, chunk_top)
    if chunk_top < max(long_lens):
        # a live slot stalls behind at most one padded chunk, strictly less
        # than the blocking path's full-prompt launches (vacuous if the CLI
        # chunk covers the longest prompt)
        assert (chk["max_prefill_launch_tokens"]
                < blk["max_prefill_launch_tokens"]), (chk, blk)

    # paged KV pool on a repeated-system-prompt trace: sharing OFF vs ON
    # over the SAME paged float path (block indirection preserves every
    # value, so the A/B isolates the prefix cache), then the quantized cold
    # tier on top.  CI tripwires: sharing engages (hit rate > 0, fewer
    # prefill launches at identical tokens) and the cold tier re-encodes
    # idle prefix blocks at ~4x fewer resident bytes, tokens unchanged.
    sys_trace = make_prefix_trace(np.random.default_rng(2), sys_n,
                                  args.arrival_rate / 3, sys_len, tail_lens,
                                  gen_lens, mcfg.vocab_size)
    paged_rows = {}
    for name, share, qbits in (("qsdp-paged-noshare", False, 0),
                               ("qsdp-paged", True, 0),
                               ("qsdp-paged-cold", True, 4)):
        r, toks, sched = bench_variant(
            name, QSDPConfig(min_quant_size=256), False, mcfg, sys_trace,
            args.slots, prefill_chunk=args.prefill_chunk,
            prefill_buckets=args.prefill_buckets, kv_block_size=kv_bs,
            kv_prefix_share=share, kv_quant_bits=qbits,
            kv_quant_horizon=16 if qbits else 0)
        out["variants"][name] = r
        outputs[name] = toks
        paged_rows[name] = (r, sched)
        show(name, r)
    nosh = out["variants"]["qsdp-paged-noshare"]
    shr = out["variants"]["qsdp-paged"]
    assert outputs["qsdp-paged"] == outputs["qsdp-paged-noshare"], \
        "prefix sharing changed a request's tokens"
    assert shr["prefix_hit_rate"] > 0, shr
    assert shr["prefill_launches"] < nosh["prefill_launches"], (shr, nosh)
    assert outputs["qsdp-paged-cold"] == outputs["qsdp-paged"], \
        "the quantized cold tier changed a request's tokens"

    # cold-tier capacity: the replay itself never demotes (the horizon
    # outlasts any mid-replay idle gap, which is why the token equality
    # above is exact).  Now idle the retired system blocks past the horizon
    # with a filler request, demote them into wire codes (~4x fewer
    # resident bytes), then resubmit the system prompt twice: the first hit
    # rehydrates from the cold store (rehydrations > 0); the second reads
    # the same rehydrated block hot and must reproduce the first's tokens
    # bit-for-bit — a demoted prefix serves DETERMINISTIC streams (the
    # codec is lossy 4-bit QDQ, so the rehydrated stream is its own
    # reference, not the full-precision row's).
    sched_cold = paged_rows["qsdp-paged-cold"][1]
    sched_cold.submit(Request(rid="cold-filler", prompt=[7, 8, 9],
                              max_new_tokens=24, seed=0))
    sched_cold.run()
    st_cold = sched_cold.stats()
    assert st_cold["demotions"] > 0, st_cold
    assert st_cold["cold_blocks"] > 0, st_cold
    hot_resident = st_cold["hot_block_bytes"] * st_cold["cold_blocks"]
    cold_ratio = hot_resident / max(st_cold["cold_bytes"], 1)
    assert cold_ratio > 3.0, (hot_resident, st_cold["cold_bytes"])
    req0 = sys_trace[0][1]
    redo = []
    for rid in ("cold-re", "cold-re2"):
        sched_cold.submit(Request(rid=rid, prompt=req0.prompt,
                                  max_new_tokens=req0.max_new_tokens,
                                  seed=req0.seed))
        redo.append(sched_cold.run()[rid].tokens.tolist())
    st_cold = sched_cold.stats()
    assert st_cold["rehydrations"] > 0, st_cold
    assert redo[0] == redo[1], \
        "rehydrated prefix served two identical requests different tokens"
    sched_cold.pool.check_invariants()
    out["variants"]["qsdp-paged-cold"].update(
        cold_blocks=int(st_cold["cold_blocks"]),
        cold_bytes=int(st_cold["cold_bytes"]),
        demotions=int(st_cold["demotions"]),
        rehydrations=int(st_cold["rehydrations"]),
        cold_compression=round(cold_ratio, 2))

    out["summary"] = {
        "gather_bytes_ratio_qsdp_vs_baseline": q / b,
        "gather_bytes_ratio_rowquant_vs_baseline": rq / b,
        "rowquant_matches_qsdp_tokens": True,
        "tokens_equal_across_variants": all(
            sum(len(t) for t in v.values())
            == sum(len(t) for t in outputs["qsdp"].values())
            for v in (outputs[k] for k in variants())),
        "chunked_matches_solo_chunked_tokens": True,
        "chunked_prefill_traces": chk["prefill_traces"],
        "blocking_prefill_traces": blk["prefill_traces"],
        "chunked_max_prefill_launch_tokens": chk["max_prefill_launch_tokens"],
        "blocking_max_prefill_launch_tokens": blk["max_prefill_launch_tokens"],
        "ttft_p95_ratio_chunked_vs_blocking": (
            round(chk["ttft_s_p95"] / max(blk["ttft_s_p95"], 1e-9), 3)),
        "paged_matches_noshare_tokens": True,
        "paged_prefix_hit_rate": shr["prefix_hit_rate"],
        "paged_prefill_launches": shr["prefill_launches"],
        "noshare_prefill_launches": nosh["prefill_launches"],
        "cold_matches_paged_tokens": True,
        "cold_compression": round(cold_ratio, 2),
        "cold_blocks": int(st_cold["cold_blocks"]),
        "spec_matches_qsdp_tokens": True,
        "spec_accepted_per_launch": out["variants"]["qsdp-spec"][
            "accepted_per_launch"],
        "spec_launches_per_token": out["variants"]["qsdp-spec"][
            "launches_per_token"],
        "spec_draft_overhead": out["variants"]["qsdp-spec"]["draft_overhead"],
    }
    print(f"qsdp ships {out['summary']['gather_bytes_ratio_qsdp_vs_baseline']:.3f}x "
          f"the baseline gather bytes per decode step at equal tokens")
    print(f"chunked prefill: {chk['prefill_traces']} traces vs "
          f"{blk['prefill_traces']} blocking for {len(long_lens)} distinct "
          f"prompt lengths; per-launch stall {chk['max_prefill_launch_tokens']}"
          f" vs {blk['max_prefill_launch_tokens']} tokens; "
          f"ttft p95 {chk['ttft_s_p95']:.3f}s vs {blk['ttft_s_p95']:.3f}s")
    print(f"paged pool: prefix hit rate {shr['prefix_hit_rate']:.2f}, "
          f"{shr['prefill_launches']} prefill launches vs "
          f"{nosh['prefill_launches']} unshared at identical tokens; cold "
          f"tier holds {st_cold['cold_blocks']} blocks at "
          f"{cold_ratio:.1f}x fewer resident bytes")
    sp = out["variants"]["qsdp-spec"]
    print(f"speculative: {sp['accepted_per_launch']:.2f} tokens/verify "
          f"launch, {sp['launches_per_token']:.2f} launches/token "
          f"(draft overhead {sp['draft_overhead']:.2f}) at tokens bit-equal "
          f"to non-speculative qsdp")

    doc = _round_floats(bench_schema.stamp(out))
    bench_schema.validate_bench_serve(doc)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
