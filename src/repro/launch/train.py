"""Training launcher (single-host; emulated multi-device CPU mesh or real
TPU slice — the same code path).

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --arch gpt-125m --smoke \
      --steps 100 --data-par 2 --model-par 4 --wbits 8 --gbits 8

Uses the deterministic synthetic Markov LM corpus (repro.data) so loss
curves are meaningful and exactly reproducible.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from .. import configs
from ..core.qsdp import MeshSpec, QSDPConfig
from ..data import SyntheticLM, make_batch
from ..models.transformer import Model
from ..optim import AdamWConfig, cosine_schedule, make_adamw
from ..train.checkpoint import save_checkpoint
from ..train.step import (
    init_train_state,
    make_jitted_train_step,
    quantize_train_state,
)
from .compile_cache import enable_compile_cache
from .mesh import make_mesh


def build_qsdp(args) -> QSDPConfig:
    if args.plan:
        from ..tune.plan import DeploymentPlan
        try:
            plan = DeploymentPlan.load(args.plan)
            plan.validate_mesh(("data", "model"),
                               (args.data_par, args.model_par))
            return plan.to_qsdp_config(QSDPConfig())
        except (OSError, ValueError) as e:
            raise SystemExit(f"--plan {args.plan}: {e}")
    if args.baseline:
        return QSDPConfig.baseline()
    return QSDPConfig(
        weight_bits=args.wbits, grad_bits=args.gbits,
        bucket_size=args.bucket, min_quant_size=args.min_quant_size,
        hierarchical=args.hierarchical,
        coalesce=args.coalesce, prefetch=args.prefetch,
        coalesce_max_bytes=args.coalesce_max_bytes,
    )


def validate_args(ap: argparse.ArgumentParser, args) -> None:
    """Reject inconsistent flag combos at parse time — tracing errors deep
    inside shard_map are unreadable; these are not."""
    if args.prefetch and not args.coalesce:
        ap.error("--prefetch requires coalescing (the prefetch pipeline "
                 "carries the coalesced u8 wire buffer through the scan); "
                 "drop --no-coalesce")
    for flag, v in (("--wbits", args.wbits), ("--gbits", args.gbits),
                    ("--master-bits", args.master_bits)):
        if not 2 <= v <= 8:
            ap.error(f"{flag} must be in 2..8 (got {v}) — the wire format "
                     f"packs 2-8 bit codes")
    if args.moment_bits is not None and not 2 <= args.moment_bits <= 8:
        ap.error(f"--moment-bits must be in 2..8 (got {args.moment_bits})")
    if args.bucket <= 0:
        ap.error(f"--bucket must be positive (got {args.bucket})")
    if args.coalesce_max_bytes is not None and args.coalesce_max_bytes < 0:
        ap.error("--coalesce-max-bytes must be >= 0 (0 = never coalesce)")
    if args.data_par < 1 or args.model_par < 1:
        ap.error("--data-par/--model-par must be >= 1")
    if args.quantize_master and args.quantized_state:
        ap.error("--quantize-master (QDQ f32 state) and --quantized-state "
                 "(wire-code state) are mutually exclusive")
    if args.plan and any([args.baseline, args.hierarchical,
                          args.coalesce_max_bytes is not None,
                          args.prefetch, not args.coalesce]):
        ap.error("--plan pins the comm policy; don't combine it with "
                 "--baseline/--hierarchical/--coalesce-max-bytes/--prefetch/"
                 "--no-coalesce")


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt-125m")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=6e-4)
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--baseline", action="store_true", help="FSDP fp baseline")
    ap.add_argument("--wbits", type=int, default=8)
    ap.add_argument("--gbits", type=int, default=8)
    ap.add_argument("--bucket", type=int, default=1024)
    ap.add_argument("--min-quant-size", type=int, default=2048)
    ap.add_argument("--hierarchical", action="store_true")
    ap.add_argument("--coalesce", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="coalesced wire format (one u8 collective per "
                         "layer gather)")
    ap.add_argument("--prefetch", action="store_true",
                    help="double-buffered layer prefetch (requires "
                         "coalescing)")
    ap.add_argument("--coalesce-max-bytes", type=int, default=None,
                    help="per-layer byte threshold: gathers whose coalesced "
                         "wire buffer exceeds this fall back to per-tensor "
                         "launches (None = always coalesce)")
    ap.add_argument("--plan", type=str, default=None,
                    help="DeploymentPlan JSON from repro.tune.autotune — "
                         "pins the whole comm policy instead of the "
                         "individual flags above")
    ap.add_argument("--quantize-master", action="store_true",
                    help="f32 state, QDQ-round-tripped through Q^w each step")
    ap.add_argument("--quantized-state", action="store_true",
                    help="theory-faithful quantized-domain state: master "
                         "weights rest as packed wire codes (QuantizedParam)")
    ap.add_argument("--master-bits", type=int, default=8)
    ap.add_argument("--moment-bits", type=int, default=None,
                    help="store Adam mu/nu as packed codes of this width")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt", type=str, default=None)
    ap.add_argument("--out-json", type=str, default=None)
    args = ap.parse_args(argv)
    validate_args(ap, args)
    return args


def train(args) -> dict:
    """Run `args.steps` steps; returns the per-log-point records (loss,
    grad norm, wall time since the first step, and seconds per step since
    the previous log point, read after ``block_until_ready``), the compile
    time, and the compiled step program."""
    nd = args.data_par * args.model_par
    assert len(jax.devices()) >= nd, (len(jax.devices()), nd)
    mesh = make_mesh((args.data_par, args.model_par), ("data", "model"))
    ms = MeshSpec(axes=("data", "model"), shape=(args.data_par, args.model_par))

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    qsdp = build_qsdp(args)
    model = Model(cfg, ms, qsdp)

    sched = cosine_schedule(args.lr, args.warmup, args.steps)
    opt = make_adamw(AdamWConfig(lr=args.lr, schedule=sched,
                                 moment_bits=args.moment_bits))
    state = init_train_state(model, opt, jax.random.PRNGKey(args.seed), mesh)
    if args.quantized_state:
        state = quantize_train_state(
            state, model, jax.random.PRNGKey(args.seed + 2),
            master_bits=args.master_bits)

    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       global_batch=args.batch, seed=args.seed)
    step = make_jitted_train_step(model, opt, mesh, n_micro=args.n_micro,
                                  quantize_master=args.quantize_master,
                                  master_bits=args.master_bits,
                                  quantized_state=args.quantized_state)

    if args.plan:
        tag = (f"QSDP plan W{qsdp.weight_bits}G{qsdp.grad_bits} "
               f"coalesce<={qsdp.coalesce_max_bytes}B"
               if qsdp.coalesce_max_bytes is not None
               else f"QSDP plan W{qsdp.weight_bits}G{qsdp.grad_bits}")
    else:
        tag = "baseline-FSDP" if args.baseline else f"QSDP W{args.wbits}G{args.gbits}"
    if args.quantized_state:
        tag += f" qstate{args.master_bits}" + (
            f"m{args.moment_bits}" if args.moment_bits else "")
    print(f"# {cfg.name} [{tag}] mesh=({args.data_par},{args.model_par}) "
          f"batch={args.batch} seq={args.seq} params~{cfg.n_params()/1e6:.1f}M "
          f"bigram-floor={data.bigram_entropy():.3f} nats")
    step_key = lambda i: jax.random.fold_in(jax.random.PRNGKey(args.seed + 1), i)
    log = []
    with mesh:
        t0 = time.time()
        # compile up front (the jitted call below reuses this executable) so
        # compilation is reported as set-up, not as step time
        compiled = step.lower(state, make_batch(data, 0, mesh, ms.fsdp_axes),
                              step_key(0)).compile()
        compile_s = time.time() - t0
        print(f"# compiled step in {compile_s:.1f}s")
        t0 = t_prev = time.time()
        i_prev = -1
        for i in range(args.steps):
            batch = make_batch(data, i, mesh, ms.fsdp_axes)
            state, m = step(state, batch, step_key(i))
            if i % args.log_every == 0 or i == args.steps - 1:
                jax.block_until_ready((state, m))
                now = time.time()
                log.append(dict(step=i, loss=float(m["loss"]),
                                gnorm=float(m["grad_norm"]), t=now - t0,
                                step_s=(now - t_prev) / (i - i_prev)))
                t_prev, i_prev = now, i
                print(f"step {i:5d} loss {log[-1]['loss']:7.4f} "
                      f"gnorm {log[-1]['gnorm']:8.3f} ({log[-1]['t']:6.1f}s, "
                      f"{log[-1]['step_s']:.3f} s/step)")
    if args.ckpt:
        save_checkpoint(args.ckpt, state, meta=dict(arch=cfg.name, steps=args.steps))
        print(f"checkpoint -> {args.ckpt}")
    if args.out_json:
        with open(args.out_json, "w") as f:
            json.dump(dict(arch=cfg.name, tag=tag, log=log), f, indent=1)
    return dict(arch=cfg.name, tag=tag, log=log, compile_s=compile_s,
                compiled=compiled, state=state)


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    train(args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
