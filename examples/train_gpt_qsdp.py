"""End-to-end driver: pre-train a ~100M-parameter GPT with QSDP for a few
hundred steps on the synthetic corpus, logging loss + communication savings.

Default is a laptop-scale run (reduced width, 300 steps) that finishes on
CPU; pass --full-width for the real gpt-125m geometry (slow on CPU, the
same config the dry-run lowers for the production mesh).

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python examples/train_gpt_qsdp.py --steps 300
"""
import argparse
import time

import jax

from repro import configs
from repro.core.qsdp import MeshSpec, QSDPConfig, step_comm_bytes
from repro.data import SyntheticLM, make_batch
from repro.launch.mesh import local_mesh_shape, make_mesh
from repro.models.config import ModelConfig
from repro.models.transformer import Model
from repro.optim import AdamWConfig, cosine_schedule, make_adamw
from repro.train.checkpoint import save_checkpoint
from repro.train.step import init_train_state, make_jitted_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--n-micro", type=int, default=4)  # paper: 4 accumulations
    ap.add_argument("--baseline", action="store_true")
    ap.add_argument("--full-width", action="store_true")
    ap.add_argument("--ckpt", type=str, default=None)
    args = ap.parse_args()

    dp, tp = local_mesh_shape()
    mesh = make_mesh((dp, tp), ("data", "model"))
    ms = MeshSpec(axes=("data", "model"), shape=(dp, tp))

    if args.full_width:
        cfg = configs.get_config("gpt-125m")
    else:  # ~8M params: same depth-ish shape, CPU-trainable
        cfg = ModelConfig(name="gpt-mini", arch_type="dense", n_layers=4,
                          d_model=384, vocab_size=8192, n_heads=8, n_kv_heads=8,
                          head_dim=48, d_ff=1024, rope_theta=10_000.0)

    qsdp = QSDPConfig.baseline() if args.baseline else QSDPConfig(min_quant_size=1024)
    model = Model(cfg, ms, qsdp)
    comm = step_comm_bytes(model.engine, gathers_per_param=2 * args.n_micro,
                           reduces_per_param=args.n_micro)
    print(f"# {cfg.name}: {cfg.n_params()/1e6:.1f}M params, "
          f"{'baseline FSDP' if args.baseline else 'QSDP W8G8'}; "
          f"per-device comm/step = {comm['total']/2**20:.1f} MiB "
          f"(weights {comm['weight_gather']/2**20:.1f} + grads {comm['grad_reduce']/2**20:.1f})")

    opt = make_adamw(AdamWConfig(lr=6e-4, schedule=cosine_schedule(6e-4, 20, args.steps)))
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       global_batch=args.batch)
    step = make_jitted_train_step(model, opt, mesh, n_micro=args.n_micro)
    t0 = time.time()
    with mesh:
        for i in range(args.steps):
            batch = make_batch(data, i, mesh, ms.fsdp_axes)
            state, m = step(state, batch, jax.random.fold_in(jax.random.PRNGKey(1), i))
            if i % 20 == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss {float(m['loss']):7.4f} "
                      f"gnorm {float(m['grad_norm']):7.3f} ({time.time()-t0:6.1f}s)")
    if args.ckpt:
        save_checkpoint(args.ckpt, state, meta=dict(arch=cfg.name, steps=args.steps))
        print("checkpoint ->", args.ckpt)


if __name__ == "__main__":
    main()
