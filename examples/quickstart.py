"""Quickstart: the QSDP public API in ~60 lines.

Builds a small GPT, shards it over the devices it finds ((2 data x 4 model)
on 8, e.g. emulated as below), runs a few quantized-communication training
steps, then generates tokens.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core.qsdp import MeshSpec, QSDPConfig
from repro.data import SyntheticLM, make_batch
from repro.launch.mesh import local_mesh_shape, make_mesh
from repro.models.decode import DecodeSpec
from repro.models.transformer import Model
from repro.optim import AdamWConfig, make_adamw
from repro.serve import ServeEngine
from repro.train.step import init_train_state, make_jitted_train_step


def main():
    # 1. mesh: ("data", "model") — FSDP over data, tensor-parallel over model
    dp, tp = local_mesh_shape()
    mesh = make_mesh((dp, tp), ("data", "model"))
    ms = MeshSpec(axes=("data", "model"), shape=(dp, tp))

    # 2. the paper's technique, as config: quantize everything FSDP transmits
    qsdp = QSDPConfig(weight_bits=8, grad_bits=8, bucket_size=1024,
                      min_quant_size=256)

    # 3. any architecture from the registry (10 assigned + GPT family)
    cfg = configs.get_smoke("gpt-125m")
    model = Model(cfg, ms, qsdp)

    # 4. train a few steps on the synthetic corpus
    opt = make_adamw(AdamWConfig(lr=1e-3))
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=128, global_batch=8)
    step = make_jitted_train_step(model, opt, mesh, n_micro=2)
    with mesh:
        for i in range(10):
            batch = make_batch(data, i, mesh, ms.fsdp_axes)
            state, m = step(state, batch, jax.random.fold_in(jax.random.PRNGKey(1), i))
            print(f"step {i}: loss={float(m['loss']):.4f}")

    # 5. serve: greedy generation with quantized weight gathers
    spec = DecodeSpec(cache_len=64 + (-64) % tp, batch_global=8,
                      batch_sharded=8 % ms.fsdp_size == 0)
    eng = ServeEngine(model, mesh, spec)
    prompt, _ = data.sample(99, batch=8, seq=32)
    with mesh:
        out = eng.generate(state.params, {"tokens": prompt},
                           {"tokens": P(ms.fsdp_axes if spec.batch_sharded else None)},
                           n_tokens=8)
    print("generated:", out[0].tolist())


if __name__ == "__main__":
    main()
