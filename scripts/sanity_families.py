"""Quick sanity: forward+backward one microbatch for each arch family on an
8-device CPU mesh. Run: PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 python scripts/sanity_families.py"""
import jax, jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core.qsdp import MeshSpec, QSDPConfig
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig
from repro.models.transformer import Model

mesh = make_mesh((2, 4), ("data", "model"))
ms = MeshSpec(axes=("data", "model"), shape=(2, 4))
qcfg = QSDPConfig(min_quant_size=256)

FAMS = {
    "dense": dict(arch_type="dense", n_layers=2, d_model=128, vocab_size=512,
                  n_heads=8, n_kv_heads=4, head_dim=16, d_ff=256),
    "dense_bias": dict(arch_type="dense", n_layers=2, d_model=128, vocab_size=512,
                       n_heads=8, n_kv_heads=8, head_dim=16, d_ff=256, qkv_bias=True),
    "moe": dict(arch_type="moe", n_layers=2, d_model=128, vocab_size=512,
                n_heads=8, n_kv_heads=4, head_dim=16, n_experts=4, moe_top_k=2, moe_d_ff=128),
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

B, S = 4, 32
for name, kw in FAMS.items():
    cfg = ModelConfig(name=name, **kw)
    m = Model(cfg, ms, qcfg)
    params = m.init_params(jax.random.PRNGKey(0))
    pspecs = m.param_pspecs()
    batch = {"tokens": jnp.ones((B, S), jnp.int32), "labels": jnp.ones((B, S), jnp.int32)}
    bspecs = {"tokens": P(("data",)), "labels": P(("data",))}
    if kw["arch_type"] == "vlm":
        batch["vision_embeds"] = jnp.zeros((B, S, 128), jnp.float32)
        batch["vision_mask"] = jnp.zeros((B, S), bool)
        batch["positions"] = jnp.broadcast_to(jnp.arange(S), (3, B, S))
        bspecs["vision_embeds"] = P(("data",)); bspecs["vision_mask"] = P(("data",))
        bspecs["positions"] = P(None, ("data",))
    if kw["arch_type"] == "audio":
        batch["audio_embeds"] = jnp.zeros((B, S // 2, 128), jnp.float32)
        bspecs["audio_embeds"] = P(("data",))

    @partial(shard_map, mesh=mesh, in_specs=(pspecs, bspecs, P()), out_specs=P(), check_vma=False)
    def step(params, batch, key):
        loss, grads = jax.value_and_grad(m.loss_fn)(params, batch, key[0])
        gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads)))
        return jax.lax.pmean(loss, ("data", "model")), jax.lax.pmax(gnorm, ("data", "model"))

    with mesh:
        loss, gnorm = jax.jit(step)(params, batch, jax.random.PRNGKey(1)[None])
    ok = bool(jnp.isfinite(loss)) and bool(jnp.isfinite(gnorm))
    print(f"{name:12s} loss={float(loss):.4f} gnorm={float(gnorm):.4f} {'OK' if ok else 'FAIL'}")
