"""Serve a model with batched requests: prefill a batch of prompts, decode
greedily, report per-step token throughput and the quantized weight-gather
bytes each decode step ships.  Engine setup is the shared
repro.serve.build_serve_setup — the launcher, this example, and
benchmarks/bench_serve.py all build the exact same stack, and the
continuous mode (--continuous) builds its scheduler through the same
serve.common.make_scheduler as the launcher (flag-for-flag parity).

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python examples/serve_batched.py --arch olmoe-1b-7b

Continuous batching with self-speculative decoding (a 4-bit draft of the
SAME weights proposes 4 tokens/slot/step, the serving-precision model
verifies them in one launch; committed tokens are bit-identical to
non-speculative decode):

  PYTHONPATH=src python examples/serve_batched.py --arch gpt-125m \
      --continuous --prefill-chunk 16 --draft-bits 4 --draft-depth 4
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qsdp import QSDPConfig
from repro.data import SyntheticLM
from repro.launch.mesh import local_mesh_shape
from repro.serve import (Request, build_serve_setup, make_prompt_batch,
                         make_scheduler)


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmoe-1b-7b")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="smoke-sized config (default for the example)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--data-par", type=int, default=0,
                    help="0 = auto: (2, 4) when 8+ devices, else (1, 1)")
    ap.add_argument("--model-par", type=int, default=0)
    ap.add_argument("--baseline", action="store_true")
    ap.add_argument("--wbits", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    # continuous-batching knobs (same set as repro.launch.serve)
    ap.add_argument("--continuous", action="store_true",
                    help="serve a request queue through the "
                         "continuous-batching scheduler")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="chunked prefill size (also the chunk size when "
                         "--kv-block-size is set in one-shot mode)")
    ap.add_argument("--prefill-buckets", type=int, default=4)
    ap.add_argument("--prefill-interleave", type=int, default=1)
    # paged KV pool knobs
    ap.add_argument("--kv-block-size", type=int, default=0,
                    help="paged KV pool block size (0 = per-slot ring); "
                         "paged serving prefills in chunks")
    ap.add_argument("--kv-pool-blocks", type=int, default=0)
    ap.add_argument("--kv-quant-bits", type=int, default=0)
    ap.add_argument("--kv-quant-horizon", type=int, default=64)
    # self-speculative decoding knobs
    ap.add_argument("--draft-bits", type=int, default=0,
                    help="bit width of the self-speculative draft forward "
                         "(0 = off; 2-4 typical)")
    ap.add_argument("--draft-depth", type=int, default=0,
                    help="draft up to this many tokens per slot per step "
                         "(<= 1 = off; requires --continuous)")
    return ap.parse_args()


def run_continuous(setup, args):
    rng = np.random.default_rng(args.seed)
    sched = make_scheduler(
        setup, gather_key=jax.random.PRNGKey(args.seed),
        prefill_chunk=args.prefill_chunk,
        prefill_buckets=args.prefill_buckets,
        prefill_interleave=args.prefill_interleave,
        kv_quant_bits=args.kv_quant_bits if args.kv_block_size else 0,
        kv_quant_horizon=args.kv_quant_horizon)
    for i in range(args.requests):
        plen = int(rng.integers(max(args.prompt_len // 2, 1),
                                args.prompt_len + 1))
        gen = int(rng.integers(max(args.gen // 2, 1), args.gen + 1))
        sched.submit(Request(
            rid=f"req{i}",
            prompt=rng.integers(0, setup.cfg.vocab_size, size=plen).tolist(),
            max_new_tokens=gen, temperature=args.temperature,
            top_k=args.top_k, seed=args.seed + i))
    t0 = time.time()
    done = sched.run()
    dt = time.time() - t0
    st = sched.stats()
    print(f"# {setup.cfg.name} continuous: {len(done)} requests, "
          f"{st['tokens_generated']} tokens in {dt:.2f}s "
          f"({st['tokens_generated'] / dt:.1f} tok/s incl. compile), "
          f"occupancy {st['mean_occupancy']:.2f}/{st['slots']}")
    if setup.spec.speculative:
        print(f"# speculative: draft {setup.spec.draft_bits}-bit x depth "
              f"{setup.spec.draft_depth} -> accepted/launch "
              f"{st['accepted_per_launch']:.2f}, launches/token "
              f"{st['launches_per_token']:.2f}")
    first = done[sorted(done)[0]]
    print("sample:", first.tokens.tolist())


def run_batch(setup, args):
    cfg, eng, params = setup.cfg, setup.engine, setup.params
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.prompt_len,
                       global_batch=args.batch, seed=args.seed)
    tokens, _ = data.sample(0)
    prompt, pspecs = make_prompt_batch(cfg, setup.spec, setup.ms, tokens)

    kw, bt = {}, ()
    if setup.spec.paged:
        # paged pool: chunked prefill + fixed gather key; the solo path
        # lays each lane out on the identity block table
        kw = dict(prefill_chunk=args.prefill_chunk, fold_step_keys=False)
        bps = setup.spec.blocks_per_slot
        bt = (jnp.arange(args.batch * bps,
                         dtype=jnp.int32).reshape(args.batch, bps),)
    with setup.mesh:
        t0 = time.time()
        out = eng.generate(params, prompt, pspecs, n_tokens=args.gen, **kw)
        out.block_until_ready()
        t_total = time.time() - t0
        # steady-state decode rate (re-run decode only)
        dec = eng.decode_step()
        cache = eng.init_cache()
        nxt = out[:, -1]
        key0 = jax.random.PRNGKey(0)
        t1 = time.time()
        for i in range(8):
            pos = jnp.full((args.batch,), args.prompt_len + i, jnp.int32)
            k = key0 if setup.spec.paged else jax.random.PRNGKey(i)
            nxt, cache = dec(params, cache, nxt, pos, *bt, k)
        nxt.block_until_ready()
        rate = 8 * args.batch / (time.time() - t1)
    print(f"generated {args.batch}x{args.gen} tokens in {t_total:.2f}s "
          f"(incl. compile); steady decode ~{rate:.1f} tok/s")
    print("sample:", out[0, :16].tolist())


def main():
    args = parse_args()
    if args.data_par and args.model_par:
        dp, tp = args.data_par, args.model_par
    else:
        dp, tp = local_mesh_shape()
    qsdp = (QSDPConfig.baseline() if args.baseline
            else QSDPConfig(weight_bits=args.wbits, min_quant_size=1024))
    if (args.draft_bits > 0) != (args.draft_depth > 1):
        raise SystemExit("speculative decode needs BOTH --draft-bits >= 2 "
                         "and --draft-depth >= 2")
    if args.draft_depth > 1 and not args.continuous:
        raise SystemExit("--draft-depth requires --continuous")
    setup = build_serve_setup(
        args.arch, data_par=dp, model_par=tp, smoke=args.smoke, qsdp=qsdp,
        batch=args.batch, prompt_len=args.prompt_len, gen=args.gen,
        seed=args.seed,
        sampling=args.continuous and (args.temperature > 0 or args.top_k > 1),
        kv_block_size=args.kv_block_size,
        kv_pool_blocks=args.kv_pool_blocks,
        draft_bits=args.draft_bits, draft_depth=args.draft_depth)

    # per-decode-step wire bytes: ONE quantized gather per parameter
    print(f"# {setup.cfg.name} "
          f"({'baseline' if args.baseline else f'QSDP W{args.wbits}'}): "
          f"decode-step weight gathers = "
          f"{setup.decode_gather_bytes() / 2**20:.2f} MiB/device")
    if args.continuous:
        run_continuous(setup, args)
    else:
        run_batch(setup, args)


if __name__ == "__main__":
    main()
