"""Shared trainer for accuracy-recovery benchmarks (Tables 1-3).

Trains a small GPT on the deterministic synthetic Markov corpus with a
given QSDP policy and returns the loss curve.  Runs on the trivial (1,1)
mesh: with FSDP size 1 the all-gathers are local but the quantize ->
dequantize of every transmitted tensor still applies, so the *accuracy*
effect of wire quantization is exactly reproduced at any device count
(bytes are accounted analytically elsewhere).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.qsdp import MeshSpec, QSDPConfig
from repro.data import SyntheticLM, make_batch
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig
from repro.models.transformer import Model
from repro.optim import AdamWConfig, cosine_schedule, make_adamw
from repro.train.step import init_train_state, make_jitted_train_step

BENCH_MODEL = ModelConfig(
    name="gpt-bench", arch_type="dense", n_layers=2, d_model=192,
    vocab_size=512, n_heads=6, n_kv_heads=6, head_dim=32, d_ff=384,
    rope_theta=10_000.0,
)


@dataclasses.dataclass
class RunResult:
    tag: str
    losses: list  # [(step, loss)]
    final_loss: float
    ppl: float


def train_run(qsdp: QSDPConfig, steps: int = 200, batch: int = 8, seq: int = 128,
              lr: float = 2e-3, seed: int = 0, tag: str = "", model_cfg=None,
              eval_last: int = 5) -> RunResult:
    cfg = model_cfg or BENCH_MODEL
    mesh = make_mesh((1, 1), ("data", "model"))
    ms = MeshSpec(axes=("data", "model"), shape=(1, 1))
    model = Model(cfg, ms, qsdp)
    opt = make_adamw(AdamWConfig(lr=lr, schedule=cosine_schedule(lr, 20, steps)))
    state = init_train_state(model, opt, jax.random.PRNGKey(seed))
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch,
                       seed=seed, branching=4)
    step = make_jitted_train_step(model, opt, mesh, n_micro=1)
    losses = []
    with mesh:
        for i in range(steps):
            b = make_batch(data, i, mesh, ms.fsdp_axes)
            state, m = step(state, b, jax.random.fold_in(jax.random.PRNGKey(seed + 1), i))
            if i % 10 == 0 or i >= steps - eval_last:
                losses.append((i, float(m["loss"])))
    tail = [l for _, l in losses[-eval_last:]]
    final = sum(tail) / len(tail)
    return RunResult(tag=tag, losses=losses, final_loss=final,
                     ppl=float(jnp.exp(jnp.asarray(final))))


def qsdp_wg(w: int | None, g: int | None, **kw) -> QSDPConfig:
    """w/g = bits or None for full precision; min_quant_size small so the
    bench model's tensors are actually quantized."""
    return QSDPConfig(
        quantize_weights=w is not None, quantize_grads=g is not None,
        weight_bits=w or 8, grad_bits=g or 8, min_quant_size=256, **kw,
    )
