"""Per-architecture smoke tests (deliverable f): a REDUCED variant of each
assigned family runs one forward/train step on CPU — output shapes + no
NaNs.  Runs on the trivial (1,1) mesh so it works on a single device."""
import jax
import jax.numpy as jnp
import pytest
from functools import partial
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro import configs
from repro.core.qsdp import MeshSpec, QSDPConfig
from repro.models.transformer import Model

MS = MeshSpec(axes=("data", "model"), shape=(1, 1))
QS = QSDPConfig(min_quant_size=256)
B, S = 2, 64


def _batch(cfg):
    batch = {
        "tokens": jnp.ones((B, S), jnp.int32),
        "labels": jnp.ones((B, S), jnp.int32),
    }
    specs = {"tokens": P(("data",)), "labels": P(("data",))}
    if cfg.arch_type == "vlm":
        batch["vision_embeds"] = jnp.zeros((B, S, cfg.d_model), jnp.bfloat16)
        batch["vision_mask"] = jnp.zeros((B, S), bool)
        batch["positions"] = jnp.broadcast_to(jnp.arange(S), (3, B, S))
        specs.update(vision_embeds=P(("data",)), vision_mask=P(("data",)),
                     positions=P(None, ("data",)))
    if cfg.arch_type == "audio":
        batch["audio_embeds"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(5), (B, S // cfg.enc_frames_ratio, cfg.d_model))
        specs["audio_embeds"] = P(("data",))
    return batch, specs


@pytest.mark.parametrize("arch", configs.list_archs())
def test_smoke_train_step(arch, mesh11):
    cfg = configs.get_smoke(arch)
    assert cfg.n_layers <= 4 and cfg.d_model <= 512
    assert cfg.n_experts <= 4
    model = Model(cfg, MS, QS)
    params = model.init_params(jax.random.PRNGKey(0))
    batch, bspecs = _batch(cfg)

    @partial(shard_map, mesh=mesh11,
             in_specs=(model.param_pspecs(), bspecs, P()),
             out_specs=(P(), model.param_pspecs()), check_vma=False)
    def step(p, b, k):
        loss, grads = jax.value_and_grad(model.loss_fn)(p, b, k)
        return loss, grads

    with mesh11:
        loss, grads = jax.jit(step)(params, batch, jax.random.PRNGKey(1))
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), float(loss)
    for name, g in grads.items():
        assert g.shape == params[name].shape, name
        assert bool(jnp.all(jnp.isfinite(g))), name


@pytest.mark.parametrize("arch", configs.ASSIGNED)
def test_full_config_matches_assignment(arch):
    """The full (non-smoke) configs carry the exact assigned hyperparams."""
    cfg = configs.get_config(arch)
    expected = {
        "qwen2_5_3b": (36, 2048, 16, 2, 11008, 151936),
        "yi_6b": (32, 4096, 32, 4, 11008, 64000),
        "seamless_m4t_large_v2": (24, 1024, 16, 16, 8192, 256206),
        "qwen1_5_32b": (64, 5120, 40, 40, 27392, 152064),
        "olmoe_1b_7b": (16, 2048, 16, 16, None, 50304),
        "yi_34b": (60, 7168, 56, 8, 20480, 64000),
        "zamba2_7b": (81, 3584, 32, 32, 14336, 32000),
        "qwen2_vl_72b": (80, 8192, 64, 8, 29568, 152064),
        "qwen3_moe_235b_a22b": (94, 4096, 64, 4, None, 151936),
        "mamba2_370m": (48, 1024, 0, 0, None, 50280),
    }[arch]
    L, d, h, kv, ff, v = expected
    assert cfg.n_layers == L and cfg.d_model == d and cfg.vocab_size == v
    assert cfg.n_heads == h and cfg.n_kv_heads == kv
    if ff is not None:
        assert cfg.d_ff == ff
    assert cfg.source  # pool citation present


def test_moe_configs_expert_counts():
    assert configs.get_config("olmoe_1b_7b").n_experts == 64
    assert configs.get_config("olmoe_1b_7b").moe_top_k == 8
    c = configs.get_config("qwen3_moe_235b_a22b")
    assert c.n_experts == 128 and c.moe_top_k == 8 and c.moe_d_ff == 1536


def test_ssm_config_state():
    assert configs.get_config("mamba2_370m").ssm_state == 128
    assert configs.get_config("zamba2_7b").ssm_state == 64
