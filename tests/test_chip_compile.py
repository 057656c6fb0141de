"""The main-path Pallas kernels compile for a TPU v5e, at real widths.

Nothing runs: the TPU compiler (installed with jax) compiles for a v5e chip
that is described, not attached, and refuses what the chip would refuse
(casts Mosaic cannot lower, blocks that break the (8, 128) rule, too much
VMEM).  Interpret mode on the CPU accepts all of those, so these compiles
are what keeps the kernels honest between chip runs.

Widths are those the GPT configs produce: 1024-value buckets, 4096 buckets
per call (a gpt-350m MLP weight), and rowquant_matmul at K=2048, N=8192
(gpt-1.3b's MLP) for decode (M=8) and prefill (M=128) rows, with per-row
(n_seg=1) and QSDP wire-layout (n_seg=N/1024) affines.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

BUCKET = 1024
N_BUCKETS = 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return fn.lower(*shapes).compile().as_text()


@pytest.mark.parametrize("bits,mode", [(8, "shift"), (4, "stochastic")])
def test_quantize_pack_compiles_for_v5e(one_chip, no_persistent_cache, bits, mode):
    x = jax.ShapeDtypeStruct((N_BUCKETS, BUCKET), jnp.float32, sharding=one_chip)
    rand_cols = BUCKET if mode == "stochastic" else 1
    rand = jax.ShapeDtypeStruct((N_BUCKETS, rand_cols), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda x, r: ops.quantize_packed(
        x, r, (1 << bits) - 1, bits, mode, 1.0, interpret=False))
    assert "tpu_custom_call" in _compiled_text(fn, x, rand)


def test_unpack_dequantize_compiles_for_v5e(one_chip, no_persistent_cache):
    bits = 4
    codes = jax.ShapeDtypeStruct((N_BUCKETS, BUCKET * bits // 8), jnp.uint8,
                                 sharding=one_chip)
    meta = jax.ShapeDtypeStruct((N_BUCKETS, 1), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda c, s, z: ops.dequantize_packed(
        c, s, z, bits, jnp.bfloat16, interpret=False))
    assert "tpu_custom_call" in _compiled_text(fn, codes, meta, meta)


@pytest.mark.parametrize("m,n_seg", [(8, 1), (128, 8192 // BUCKET)])
def test_rowquant_matmul_compiles_for_v5e(one_chip, no_persistent_cache, m, n_seg):
    k, n = 2048, 8192
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    codes = jax.ShapeDtypeStruct((k, n), jnp.uint8, sharding=one_chip)
    meta = jax.ShapeDtypeStruct((k, n_seg), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda x, c, s, z: ops.rowquant_matmul(x, c, s, z, interpret=False))
    assert "tpu_custom_call" in _compiled_text(fn, x, codes, meta, meta)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_paged_steps_keep_the_kv_pool_in_place(topo, no_persistent_cache, program):
    """The paged serving steps update the KV pool where it lies: the
    compiled step holds no copy of a whole pool.  With head_dim (64) as the
    stored minor axis the chip's compiler kept the pool in a layout padded
    to the 128-lane tile, and copied each pool in and out of every step at
    twice its size; the pool's (L, R, S, n_kv * hd) form leaves no copy."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.qsdp import MeshSpec, QSDPConfig
    from repro.launch.mesh import make_mesh
    from repro.models.config import ModelConfig
    from repro.models.decode import DecodeSpec
    from repro.models.transformer import Model
    from repro.serve.engine import ServeEngine

    mesh = make_mesh((1, 1), ("data", "model"), devices=[topo.devices[0]])
    cfg = ModelConfig(name="pool", arch_type="dense", n_layers=2, d_model=256,
                      vocab_size=512, n_heads=4, n_kv_heads=4, head_dim=64, d_ff=512)
    model = Model(cfg, MeshSpec(axes=("data", "model"), shape=(1, 1)),
                  QSDPConfig(min_quant_size=256, bucket_size=128))
    spec = DecodeSpec(cache_len=256, batch_global=4, batch_sharded=False,
                      kv_block_size=16, kv_pool_blocks=64)
    eng = ServeEngine(model, mesh, spec)

    def on_chip(s, p):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, p))

    params = {n: on_chip(s, eng._pspecs[n]) for n, s in
              jax.eval_shape(model.init_params, jax.random.PRNGKey(0)).items()}
    structs, pspecs = eng.dm.cache_struct()
    cache = {k: on_chip(s, pspecs[k]) for k, s in structs.items()}
    lanes = on_chip(jax.ShapeDtypeStruct((4,), jnp.int32), P())
    bt = on_chip(jax.ShapeDtypeStruct((4, spec.blocks_per_slot), jnp.int32), P())
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32), P())
    if program == "decode":
        text = _compiled_text(eng.decode_step(), params, cache, lanes, lanes, bt, key)
    else:
        tokens = on_chip(jax.ShapeDtypeStruct((4, 16), jnp.int32), P())
        text = _compiled_text(eng.prefill_chunk_step(16), params, cache, tokens,
                              lanes, lanes, bt, key)
    pool = "bf16[" + ",".join(map(str, structs["k"].shape)) + "]"
    copies = [line for line in text.split("\n")
              if " copy(" in line and line.split("=", 1)[1].lstrip().startswith(pool)]
    assert copies == []
