"""Sanity: train step (grad accum + AdamW) and prefill->decode for each family.
Run: PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 python scripts/sanity_serve.py
"""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.qsdp import MeshSpec, QSDPConfig
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig
from repro.models.transformer import Model
from repro.models.decode import DecodeModel, DecodeSpec
from repro.serve.engine import ServeEngine
from repro.optim import AdamWConfig, make_adamw
from repro.train.step import init_train_state, make_jitted_train_step
from repro.data import SyntheticLM

mesh = make_mesh((2, 4), ("data", "model"))
ms = MeshSpec(axes=("data", "model"), shape=(2, 4))
qcfg = QSDPConfig(min_quant_size=256)

FAMS = {
    "dense": dict(arch_type="dense", n_layers=2, d_model=128, vocab_size=512,
                  n_heads=8, n_kv_heads=4, head_dim=16, d_ff=256),
    "moe": dict(arch_type="moe", n_layers=2, d_model=128, vocab_size=512,
                n_heads=8, n_kv_heads=16, head_dim=16, n_experts=4, moe_top_k=2, moe_d_ff=128),
    "ssm": dict(arch_type="ssm", n_layers=2, d_model=128, vocab_size=512,
                ssm_state=16, ssm_head_dim=16, ssm_chunk=16),
    "hybrid": dict(arch_type="hybrid", n_layers=3, d_model=128, vocab_size=512,
                   n_heads=8, n_kv_heads=8, head_dim=16, d_ff=256,
                   ssm_state=16, ssm_head_dim=16, ssm_chunk=16, hybrid_attn_every=2),
    "vlm": dict(arch_type="vlm", n_layers=2, d_model=128, vocab_size=512,
                n_heads=8, n_kv_heads=4, head_dim=16, d_ff=256, rope_mode="mrope",
                mrope_sections=(4, 2, 2)),
    "audio": dict(arch_type="audio", n_layers=2, n_enc_layers=2, d_model=128, vocab_size=512,
                  n_heads=8, n_kv_heads=8, head_dim=16, d_ff=256, tie_embeddings=False),
}

B, S = 8, 32
for name, kw in FAMS.items():
    cfg = ModelConfig(name=name, **kw)
    m = Model(cfg, ms, qcfg)
    opt = make_adamw(AdamWConfig(lr=1e-3))
    state = init_train_state(m, opt, jax.random.PRNGKey(0))

    data = SyntheticLM(vocab_size=512, seq_len=S, global_batch=B, seed=1)
    tokens, labels = data.sample(0)
    batch = {"tokens": tokens, "labels": labels}
    bspecs = {"tokens": P(("data",)), "labels": P(("data",))}
    if kw["arch_type"] == "vlm":
        batch["vision_embeds"] = jnp.zeros((B, S, 128), jnp.float32)
        batch["vision_mask"] = jnp.zeros((B, S), bool)
        batch["positions"] = jnp.broadcast_to(jnp.arange(S), (3, B, S))
        bspecs.update(vision_embeds=P(("data",)), vision_mask=P(("data",)),
                      positions=P(None, ("data",)))
    if kw["arch_type"] == "audio":
        batch["audio_embeds"] = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (B, 16, 128))
        bspecs["audio_embeds"] = P(("data",))

    step = make_jitted_train_step(m, opt, mesh, n_micro=2, batch_pspec=bspecs)
    with mesh:
        l0 = None
        for i in range(3):
            state, metrics = step(state, batch, jax.random.fold_in(jax.random.PRNGKey(7), i))
            if l0 is None:
                l0 = float(metrics["loss"])
        l1 = float(metrics["loss"])
    print(f"{name:8s} train: loss {l0:.4f} -> {l1:.4f}  gnorm {float(metrics['grad_norm']):.3f}")

    # ---- serve: prefill + 4 decode steps ----
    spec = DecodeSpec(cache_len=0 if kw["arch_type"] == "ssm" else S,
                      batch_global=B, batch_sharded=True,
                      enc_len=16 if kw["arch_type"] == "audio" else 0)
    eng = ServeEngine(m, mesh, spec)
    prompt = dict(batch)
    prompt.pop("labels")
    ps = dict(bspecs); ps.pop("labels")
    with mesh:
        toks = eng.generate(state.params, prompt, ps, n_tokens=4)
    ok = bool(jnp.all((toks >= 0) & (toks < 512)))
    print(f"{name:8s} serve: tokens shape {toks.shape} ok={ok} sample={toks[0].tolist()}")
