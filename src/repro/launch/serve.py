"""Serving launcher: batched generation with QSDP weight gathers.

One-shot batch mode (prefill one batch, decode to completion):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.serve --arch gpt-125m --smoke \\
      --batch 8 --prompt-len 32 --gen 16 --data-par 2 --model-par 4

Continuous-batching mode (--continuous): a request queue drained through
serve.ContinuousScheduler — a fixed pool of --batch decode slots, requests
admitted into freed slots mid-decode, per-request sampling:

  PYTHONPATH=src python -m repro.launch.serve --arch gpt-125m --smoke \\
      --continuous --batch 4 --requests 16 --gen 16 --temperature 0.8 --top-k 40
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..core.qsdp import QSDPConfig
from ..data import SyntheticLM
from ..serve import (Request, build_serve_setup, make_prompt_batch,
                     make_scheduler)
from .compile_cache import enable_compile_cache


def _build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--plan", type=str, default=None,
                    help="DeploymentPlan JSON from repro.tune.autotune — "
                         "supplies the QSDP comm policy and the serve-knob "
                         "defaults (explicit flags still override knobs)")
    ap.add_argument("--arch", default="gpt-125m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8,
                    help="batch size (one-shot) / decode-slot pool size "
                         "(--continuous)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--baseline", action="store_true")
    ap.add_argument("--wbits", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    # continuous-batching flags
    ap.add_argument("--continuous", action="store_true",
                    help="serve a request queue through the "
                         "continuous-batching scheduler")
    ap.add_argument("--requests", type=int, default=16,
                    help="--continuous: number of queued requests")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="--continuous: per-request sampling temperature "
                         "(0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="--continuous: per-request top-k (0 = full vocab)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="--continuous: prefill at most this many prompt "
                         "tokens per scheduler step (0 = blocking "
                         "whole-prompt admission)")
    ap.add_argument("--prefill-buckets", type=int, default=4,
                    help="--continuous: chunk length buckets — bounds the "
                         "chunked-prefill jit cache at this many traces")
    ap.add_argument("--prefill-interleave", type=int, default=1,
                    help="--continuous: chunk launches per scheduler step "
                         "(fairness knob; 1 = maximally decode-fair)")
    # paged KV pool flags
    ap.add_argument("--kv-block-size", type=int, default=0,
                    help="paged KV pool block size in tokens (0 = per-slot "
                         "ring; > 0 requires --prefill-chunk and enables "
                         "prefix sharing)")
    ap.add_argument("--kv-pool-blocks", type=int, default=0,
                    help="physical KV blocks in the pool (0 = one full "
                         "logical window per slot)")
    ap.add_argument("--kv-quant-bits", type=int, default=0,
                    help="--continuous paged: re-encode idle cached prefix "
                         "blocks into the core.quant wire format at this "
                         "bit width (0 = cold tier off)")
    ap.add_argument("--kv-quant-horizon", type=int, default=64,
                    help="--continuous paged: idle scheduler steps before a "
                         "cached block demotes to the cold tier")
    # self-speculative decoding flags
    ap.add_argument("--draft-bits", type=int, default=0,
                    help="--continuous: bit width of the self-speculative "
                         "draft forward (0 = off; 2-4 typical) — the draft "
                         "re-quantizes the SAME weights, no second model")
    ap.add_argument("--draft-depth", type=int, default=0,
                    help="--continuous: draft up to this many tokens per "
                         "slot per step, batch-verified in one "
                         "serving-precision launch (<= 1 = off)")
    return ap


# plan serve-section field -> launcher flag dest
_PLAN_SERVE_DESTS = {
    "slots": "batch", "prefill_chunk": "prefill_chunk",
    "prefill_buckets": "prefill_buckets",
    "prefill_interleave": "prefill_interleave",
    "kv_block_size": "kv_block_size", "kv_pool_blocks": "kv_pool_blocks",
    "kv_quant_bits": "kv_quant_bits", "kv_quant_horizon": "kv_quant_horizon",
    "draft_bits": "draft_bits", "draft_depth": "draft_depth",
}


def validate_args(ap, args) -> None:
    """Reject inconsistent flag combos at parse time — failing here with a
    one-line reason beats failing deep inside tracing."""
    if not 2 <= args.wbits <= 8:
        ap.error(f"--wbits must be in 2..8 (got {args.wbits})")
    if args.draft_bits and not 2 <= args.draft_bits <= 8:
        ap.error(f"--draft-bits must be 0 (off) or in 2..8 (got "
                 f"{args.draft_bits}) — the draft re-quantizes the serving "
                 f"weights through the 2-8 bit wire kernels")
    if args.kv_quant_bits and not 2 <= args.kv_quant_bits <= 8:
        ap.error(f"--kv-quant-bits must be 0 (off) or in 2..8 "
                 f"(got {args.kv_quant_bits})")
    if args.prefill_buckets < 1:
        ap.error(f"--prefill-buckets must be >= 1 (got "
                 f"{args.prefill_buckets})")
    if min(args.prefill_chunk, args.kv_block_size, args.kv_pool_blocks,
           args.prefill_interleave - 1) < 0:
        ap.error("--prefill-chunk/--kv-block-size/--kv-pool-blocks must be "
                 ">= 0 and --prefill-interleave >= 1")
    if args.kv_block_size and not args.prefill_chunk:
        ap.error("--kv-block-size requires --prefill-chunk (paged serving "
                 "admits through chunked prefill)")
    if args.kv_quant_bits and not args.kv_block_size:
        ap.error("--kv-quant-bits requires --kv-block-size (the cold tier "
                 "demotes paged pool blocks)")
    if (args.draft_bits > 0) != (args.draft_depth > 1):
        ap.error("speculative decode needs BOTH --draft-bits >= 2 and "
                 "--draft-depth >= 2")
    if args.draft_depth > 1 and not args.continuous:
        ap.error("--draft-depth requires --continuous (speculation lives in "
                 "the scheduler's draft/verify phases)")
    if args.plan and args.baseline:
        ap.error("--plan pins the QSDP comm policy; don't combine it with "
                 "--baseline")


def parse_args(argv=None):
    ap = _build_parser()
    args = ap.parse_args(argv)
    args.plan_obj = None
    if args.plan:
        from ..tune.plan import DeploymentPlan
        try:
            plan = DeploymentPlan.load(args.plan)
        except (OSError, ValueError) as e:
            ap.error(f"--plan {args.plan}: {e}")
        # the plan's serve section provides the DEFAULTS; flags the user
        # typed still win (argparse re-parse with updated defaults)
        knobs = plan.serve_knobs()
        ap.set_defaults(**{_PLAN_SERVE_DESTS[k]: v for k, v in knobs.items()
                           if k in _PLAN_SERVE_DESTS})
        args = ap.parse_args(argv)
        args.plan_obj = plan
    validate_args(ap, args)
    return args


def run_continuous(setup, args) -> int:
    rng = np.random.default_rng(args.seed)
    sched = make_scheduler(
        setup, gather_key=jax.random.PRNGKey(args.seed),
        prefill_chunk=args.prefill_chunk,
        prefill_buckets=args.prefill_buckets,
        prefill_interleave=args.prefill_interleave,
        kv_quant_bits=args.kv_quant_bits if args.kv_block_size else 0,
        kv_quant_horizon=args.kv_quant_horizon)
    # mixed prompt/gen lengths, seeded: realistic heavy-traffic shape
    for i in range(args.requests):
        plen = int(rng.integers(max(args.prompt_len // 2, 1), args.prompt_len + 1))
        gen = int(rng.integers(max(args.gen // 2, 1), args.gen + 1))
        sched.submit(Request(
            rid=f"req{i}", prompt=rng.integers(0, setup.cfg.vocab_size,
                                               size=plen).tolist(),
            max_new_tokens=gen, temperature=args.temperature,
            top_k=args.top_k, seed=args.seed + i))
    t0 = time.time()
    done = sched.run()
    dt = time.time() - t0
    st = sched.stats()
    lat = [c.finish_step - c.submit_step for c in done.values()]
    ttft = [c.first_token_time - c.submit_time for c in done.values()]
    n_first = max(1, st["first_tokens"])
    print(f"# {setup.cfg.name} continuous: {len(done)} requests, "
          f"{st['tokens_generated']} tokens in {dt:.2f}s "
          f"({st['tokens_generated'] / dt:.1f} tok/s incl. compile), "
          f"occupancy {st['mean_occupancy']:.2f}/{st['slots']}, "
          f"latency p50={np.percentile(lat, 50):.0f} "
          f"p95={np.percentile(lat, 95):.0f} steps, "
          f"ttft p95={np.percentile(ttft, 95):.3f}s "
          f"(mean queue wait {st['queue_wait_s'] / n_first:.3f}s, "
          f"prefill wait {st['prefill_wait_s'] / n_first:.3f}s), "
          f"weight encodes {st['weight_encodes']}")
    if args.prefill_chunk:
        print(f"# chunked prefill: chunk={args.prefill_chunk} "
              f"buckets={sched.buckets} -> {st['prefill_chunks']} chunk "
              f"launches, {st['prefill_traces']} compiled prefill shapes")
    if sched.pool is not None:
        print(f"# paged KV pool: {st['blocks_total']} blocks x "
              f"{setup.spec.kv_block_size} tok, prefix hit rate "
              f"{st['prefix_hit_rate']:.2f}, cow forks {st['cow_forks']}, "
              f"cold blocks {st['cold_blocks']} "
              f"(effective capacity {st['effective_capacity']:.0f} blocks)")
    if setup.spec.speculative:
        print(f"# speculative: draft {setup.spec.draft_bits}-bit x depth "
              f"{setup.spec.draft_depth} -> accepted/launch "
              f"{st['accepted_per_launch']:.2f}, launches/token "
              f"{st['launches_per_token']:.2f}, draft overhead "
              f"{st['draft_overhead']:.2f} draft lane-steps/token")
    print(f"# decode-step weight gathers = "
          f"{setup.decode_gather_bytes() / 2**20:.2f} MiB/device")
    first = done[sorted(done)[0]]
    print("sample:", first.tokens.tolist())
    return 0


def run_batch(setup, args) -> int:
    data = SyntheticLM(vocab_size=setup.cfg.vocab_size, seq_len=args.prompt_len,
                       global_batch=args.batch, seed=args.seed)
    tokens, _ = data.sample(0)
    prompt, pspecs = make_prompt_batch(setup.cfg, setup.spec, setup.ms, tokens)
    kw = {}
    if setup.spec.paged:
        # paged serving admits through chunked prefill, which serves a
        # FIXED quantized model (one gather key)
        kw = dict(prefill_chunk=args.prefill_chunk, fold_step_keys=False)
    t0 = time.time()
    with setup.mesh:
        out = setup.engine.generate(setup.params, prompt, pspecs,
                                    n_tokens=args.gen, **kw)
    out.block_until_ready()
    dt = time.time() - t0
    print(f"# {setup.cfg.name} generated {args.batch}x{args.gen} tokens in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s incl. compile)")
    print("sample:", out[0].tolist())
    return 0


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    if args.plan_obj is not None:
        try:
            args.plan_obj.validate_mesh(("data", "model"),
                                        (args.data_par, args.model_par))
            qsdp = args.plan_obj.to_qsdp_config(QSDPConfig())
        except ValueError as e:
            raise SystemExit(f"--plan {args.plan}: {e}")
    elif args.baseline:
        qsdp = QSDPConfig.baseline()
    else:
        qsdp = QSDPConfig(weight_bits=args.wbits)
    setup = build_serve_setup(
        args.arch, data_par=args.data_par, model_par=args.model_par,
        smoke=args.smoke, qsdp=qsdp, batch=args.batch,
        prompt_len=args.prompt_len, gen=args.gen, seed=args.seed,
        sampling=args.continuous and (args.temperature > 0 or args.top_k > 1),
        kv_block_size=args.kv_block_size,
        kv_pool_blocks=args.kv_pool_blocks,
        draft_bits=args.draft_bits, draft_depth=args.draft_depth)
    if args.continuous:
        return run_continuous(setup, args)
    return run_batch(setup, args)


if __name__ == "__main__":
    raise SystemExit(main())
