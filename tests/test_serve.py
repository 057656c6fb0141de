"""Serving path unit tests on the trivial mesh: cache structs, prefill ->
decode flow, ring-buffer semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core.qsdp import MeshSpec, QSDPConfig
from repro.models.config import ModelConfig
from repro.models.decode import DecodeModel, DecodeSpec
from repro.models.transformer import Model
from repro.serve import ServeEngine

MS = MeshSpec(axes=("data", "model"), shape=(1, 1))
QS = QSDPConfig.baseline()


def _model(arch_type="dense", **kw):
    base = dict(name="t", arch_type=arch_type, n_layers=2, d_model=64,
                vocab_size=256)
    if arch_type in ("dense", "vlm", "moe", "audio", "hybrid"):
        base.update(n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128)
    if arch_type in ("ssm", "hybrid"):
        base.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=8)
    base.update(kw)
    return Model(ModelConfig(**base), MS, QS)


def test_cache_struct_shapes():
    m = _model()
    dm = DecodeModel(m, DecodeSpec(cache_len=32, batch_global=4, batch_sharded=True))
    structs, specs = dm.cache_struct()
    # (L, B, S, n_kv * hd): heads and head_dim share the last axis
    assert structs["k"].shape == (2, 4, 32, 2 * 16)
    assert structs["k"].dtype == jnp.bfloat16
    assert set(structs) == set(specs) == {"k", "v"}


def test_cache_struct_ssm():
    m = _model("ssm")
    dm = DecodeModel(m, DecodeSpec(cache_len=0, batch_global=4, batch_sharded=True))
    structs, _ = dm.cache_struct()
    # conv: (L, B, K-1, d_inner + 2N); ssm: (L, B, H, P, N)
    assert structs["conv"].shape == (2, 4, 3, 128 + 32)
    assert structs["ssm"].shape == (2, 4, 8, 16, 16)


def test_cache_struct_hybrid_groups():
    m = _model("hybrid", n_layers=5, hybrid_attn_every=2)
    dm = DecodeModel(m, DecodeSpec(cache_len=32, batch_global=2, batch_sharded=True))
    structs, _ = dm.cache_struct()
    assert structs["shared_k"].shape[0] == 2  # 5 // 2 groups
    assert structs["conv"].shape[0] == 5


def test_generate_then_extend_consistency(mesh11):
    """Greedy generate(k) tokens == generate(k+2)'s first k tokens (the
    decode chain is deterministic in the fp path)."""
    m = _model()
    params = m.init_params(jax.random.PRNGKey(0))
    spec = DecodeSpec(cache_len=32, batch_global=4, batch_sharded=True)
    prompt = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 256)}
    ps = {"tokens": P(("data",))}
    with mesh11:
        e1 = ServeEngine(m, mesh11, spec)
        t1 = np.asarray(jax.device_get(e1.generate(params, prompt, ps, n_tokens=4)))
        e2 = ServeEngine(m, mesh11, spec)
        t2 = np.asarray(jax.device_get(e2.generate(params, prompt, ps, n_tokens=6)))
    np.testing.assert_array_equal(t1, t2[:, :4])


def test_sliding_window_ring_wraps(mesh11):
    """Decode past the window size keeps working (ring overwrite) and only
    attends to the last `window` positions."""
    m = _model(sliding_window=0, long_context="sliding_window",
               long_context_window=16)
    params = m.init_params(jax.random.PRNGKey(0))
    spec = DecodeSpec(cache_len=16, batch_global=2, batch_sharded=True)
    eng = ServeEngine(m, mesh11, spec)
    prompt = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0, 256)}
    with mesh11:
        out = eng.generate(params, prompt, {"tokens": P(("data",))}, n_tokens=10)
    out = np.asarray(jax.device_get(out))
    assert out.shape == (2, 10)
    assert ((out >= 0) & (out < 256)).all()


@pytest.mark.parametrize("arch", ["qwen2_5_3b", "olmoe_1b_7b", "mamba2_370m",
                                  "zamba2_7b", "seamless_m4t_large_v2",
                                  "qwen2_vl_72b"])
def test_smoke_serve_all_families(arch, mesh11):
    """One prefill + one decode step per family's smoke config."""
    cfg = configs.get_smoke(arch)
    m = Model(cfg, MS, QSDPConfig(min_quant_size=256))
    params = m.init_params(jax.random.PRNGKey(0))
    B, S = 2, 16
    spec = DecodeSpec(cache_len=0 if cfg.arch_type == "ssm" else 32,
                      batch_global=B, batch_sharded=True,
                      enc_len=8 if cfg.arch_type == "audio" else 0)
    eng = ServeEngine(m, mesh11, spec)
    prompt = {"tokens": jnp.ones((B, S), jnp.int32)}
    ps = {"tokens": P(("data",))}
    if cfg.arch_type == "vlm":
        prompt.update(vision_embeds=jnp.zeros((B, S, cfg.d_model), jnp.bfloat16),
                      vision_mask=jnp.zeros((B, S), bool),
                      positions=jnp.broadcast_to(jnp.arange(S), (3, B, S)))
        ps.update(vision_embeds=P(("data",)), vision_mask=P(("data",)),
                  positions=P(None, ("data",)))
    if cfg.arch_type == "audio":
        prompt["audio_embeds"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(1), (B, 8, cfg.d_model), jnp.bfloat16)
        ps["audio_embeds"] = P(("data",))
    with mesh11:
        out = eng.generate(params, prompt, ps, n_tokens=2)
    out = np.asarray(jax.device_get(out))
    assert out.shape == (B, 2)
    assert ((out >= 0) & (out < cfg.vocab_size)).all()
