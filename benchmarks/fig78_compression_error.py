"""Benchmark: Figures 7/8 — compression error (relative L2) of uniform vs
learned quantization levels tracked OVER TRAINING.

The paper learns levels once after warmup and shows (i) learned error stays
below uniform for the whole run and (ii) both curves drift together, so one
learning pass suffices.  We track an attention projection and the LM head
(embedding) of the bench GPT at 4-bit weights.
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp

from repro.core.levels import (
    LevelsConfig, compression_error, dequantize_levels,
    learn_levels_for_tensor, quantize_levels, uniform_levels,
)
from repro.core.qsdp import MeshSpec
from repro.data import SyntheticLM, make_batch
from repro.launch.mesh import make_mesh
from repro.models.transformer import Model
from repro.optim import AdamWConfig, cosine_schedule, make_adamw
from repro.train.step import init_train_state, make_jitted_train_step
from ._trainer import BENCH_MODEL, qsdp_wg

BITS = 4
TRACK = ["layers/wq", "embed"]


def main(argv=None, out_dir="results/bench"):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=160)
    ap.add_argument("--every", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=40)
    args = ap.parse_args(argv)
    os.makedirs(out_dir, exist_ok=True)

    mesh = make_mesh((1, 1), ("data", "model"))
    ms = MeshSpec(axes=("data", "model"), shape=(1, 1))
    model = Model(BENCH_MODEL, ms, qsdp_wg(8, 8))
    opt = make_adamw(AdamWConfig(lr=1e-3, schedule=cosine_schedule(1e-3, 20, args.steps)))
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    data = SyntheticLM(vocab_size=BENCH_MODEL.vocab_size, seq_len=128,
                       global_batch=16, seed=0)
    step = make_jitted_train_step(model, opt, mesh, n_micro=1)

    levels = {k: None for k in TRACK}  # learned at warmup, then frozen
    curves = {k: [] for k in TRACK}

    def measure(i, params):
        for k in TRACK:
            w = params[k].reshape(-1)
            if levels[k] is None and i >= args.warmup:
                levels[k] = learn_levels_for_tensor(w, LevelsConfig(bits=BITS, epochs=2))
            qu = quantize_levels(w, uniform_levels(BITS))
            eu = float(compression_error(w, dequantize_levels(qu, uniform_levels(BITS))))
            if levels[k] is not None:
                ql = quantize_levels(w, levels[k])
                el = float(compression_error(w, dequantize_levels(ql, levels[k])))
            else:
                el = None
            curves[k].append(dict(step=i, uniform=eu, learned=el))

    with mesh:
        for i in range(args.steps):
            if i % args.every == 0:
                measure(i, state.params)
            b = make_batch(data, i, mesh, ms.fsdp_axes)
            state, m = step(state, b, jax.random.fold_in(jax.random.PRNGKey(1), i))
        measure(args.steps, state.params)

    print(f"# Figures 7/8: relative L2 compression error at {BITS}-bit weights")
    ok = True
    for k in TRACK:
        print(f"\n{k}:")
        for c in curves[k]:
            l = "     -" if c["learned"] is None else f"{c['learned']:.4f}"
            print(f"  step {c['step']:4d}  uniform={c['uniform']:.4f}  learned={l}")
        post = [c for c in curves[k] if c["learned"] is not None]
        wins = sum(c["learned"] < c["uniform"] for c in post)
        print(f"  learned < uniform at {wins}/{len(post)} checkpoints after warmup")
        ok &= wins >= 0.7 * len(post)

    with open(os.path.join(out_dir, "fig78_compression_error.json"), "w") as f:
        json.dump(curves, f, indent=1)
    print("fig78:", "PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
