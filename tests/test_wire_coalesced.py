"""Coalesced wire format: single-device tier-1 coverage.

Multi-device bit-exactness of the coalesced collectives and the HLO
launch-count regression run in a subprocess (scripts/check_coalesced.py via
test_distributed.py); here we cover everything that doesn't need devices:

  * wire_pack / wire_unpack round-trip is bit-exact for every (bits, mode,
    backend) combination, and wire_segment_bytes matches the buffer length
  * fp payload segments round-trip bit-exactly (f32) / to bf16 precision
  * meta_wire_dtype accounting in gather/reduce-scatter wire bytes
  * engine-level equivalence on the (1,1) mesh: coalesce and prefetch are
    bit-exact vs. the per-tensor path through a full loss/grad computation
  * the decode_attend irregular-GQA fix (n_kv > n_heads)
  * analyze_hlo per-dtype launch counts
"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import collectives as coll
from repro.core.qsdp import MeshSpec, QSDPConfig, layer_gather_launches
from repro.core.quant import (
    QuantConfig,
    dequantize,
    fp_pack,
    fp_unpack,
    quantize,
    wire_pack,
    wire_segment_bytes,
    wire_unpack,
)
from repro.models.config import ModelConfig
from repro.models.transformer import Model


@pytest.mark.parametrize("bits", [2, 3, 4, 5, 6, 7, 8])
@pytest.mark.parametrize("mode", ["shift", "stochastic", "nearest"])
def test_wire_roundtrip_bitexact(bits, mode, key):
    cfg = QuantConfig(bits=bits, bucket_size=64, mode=mode)
    x = jax.random.normal(key, (3, 70))  # pads to 5 buckets of 64
    q = quantize(x, cfg, key)
    buf = wire_pack(q)
    assert buf.dtype == jnp.uint8 and buf.ndim == 1
    assert buf.shape[0] == wire_segment_bytes(x.size, cfg)
    q2 = wire_unpack(buf, x.size, cfg, shape=x.shape)
    assert (np.asarray(q.codes) == np.asarray(q2.codes)).all()
    assert (np.asarray(q.scale) == np.asarray(q2.scale)).all()
    assert (np.asarray(q.zero) == np.asarray(q2.zero)).all()
    assert (np.asarray(dequantize(q)) == np.asarray(dequantize(q2))).all()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_wire_roundtrip_backends(backend, key):
    cfg = QuantConfig(bits=4, bucket_size=64, mode="stochastic", backend=backend)
    x = jax.random.normal(key, (256,))
    q = quantize(x, cfg, key)
    q2 = wire_unpack(wire_pack(q), x.size, cfg)
    assert (np.asarray(q.codes) == np.asarray(q2.codes)).all()
    assert (np.asarray(dequantize(q, backend=backend))
            == np.asarray(dequantize(q2, backend=backend))).all()


def test_wire_roundtrip_bf16_meta(key):
    cfg = QuantConfig(bits=8, bucket_size=64, mode="nearest",
                      meta_dtype="bfloat16")
    x = jax.random.normal(key, (256,))
    q = quantize(x, cfg, key)
    buf = wire_pack(q)
    assert buf.shape[0] == wire_segment_bytes(x.size, cfg)
    assert wire_segment_bytes(x.size, cfg) == 256 + 2 * 2 * 4  # codes + meta
    q2 = wire_unpack(buf, x.size, cfg)
    # round-trip through bf16 == one bf16 rounding of the f32 metadata
    assert (np.asarray(q2.scale)
            == np.asarray(q.scale.astype(jnp.bfloat16).astype(jnp.float32))).all()


def test_fp_segment_roundtrip(key):
    x = jax.random.normal(key, (100,))
    assert (np.asarray(fp_unpack(fp_pack(x, "float32"), 100, "float32"))
            == np.asarray(x)).all()
    b16 = fp_unpack(fp_pack(x, "bfloat16"), 100, "bfloat16")
    assert (np.asarray(b16)
            == np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32))).all()


def test_meta_dtype_wire_accounting():
    f32 = QuantConfig(bits=8, bucket_size=256)
    b16 = dataclasses.replace(f32, meta_dtype="bfloat16")
    n, p = 4096, 8
    nb = n // 256
    assert coll.gather_wire_bytes(n, p, f32) - coll.gather_wire_bytes(n, p, b16) \
        == (p - 1) * 2 * 2 * nb
    assert coll.reduce_scatter_wire_bytes(n * p, p, f32) \
        - coll.reduce_scatter_wire_bytes(n * p, p, b16) == (p - 1) * 2 * 2 * nb


def test_wire_layout_offsets():
    cfg = QuantConfig(bits=4, bucket_size=64)
    lo = coll.WireLayout((coll.WireSegment(128, cfg),
                          coll.WireSegment(10, None, "float32"),
                          coll.WireSegment(10, None, "bfloat16")))
    assert lo.offsets() == [0, lo.segments[0].nbytes,
                            lo.segments[0].nbytes + 40]
    assert lo.nbytes == lo.segments[0].nbytes + 40 + 20


# ---------------------------------------------------------------------------
# Engine-level equivalence on the trivial (1,1) mesh
# ---------------------------------------------------------------------------

MCFG = ModelConfig(name="t", arch_type="dense", n_layers=3, d_model=64,
                   vocab_size=256, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128)
MS11 = MeshSpec(axes=("data", "model"), shape=(1, 1))


def _loss_and_grads(mesh11, qcfg, mcfg=MCFG):
    model = Model(mcfg, MS11, qcfg)
    params = model.init_params(jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 256)
    batch = {"tokens": tokens, "labels": tokens}

    @partial(shard_map, mesh=mesh11,
             in_specs=(model.param_pspecs(),
                       {"tokens": P(("data",)), "labels": P(("data",))}, P()),
             out_specs=(P(), model.param_pspecs()), check_vma=False)
    def f(p, b, k):
        loss, g = jax.value_and_grad(model.loss_fn)(p, b, k)
        return jax.lax.pmean(loss, ("data", "model")), g

    loss, g = jax.jit(f)(params, batch, jax.random.PRNGKey(3))
    return float(loss), jax.device_get(g)


def test_engine_coalesce_prefetch_bitexact(mesh11):
    base = QSDPConfig(min_quant_size=128, coalesce=False)
    l0, g0 = _loss_and_grads(mesh11, base)
    l1, g1 = _loss_and_grads(mesh11, dataclasses.replace(base, coalesce=True))
    l2, g2 = _loss_and_grads(mesh11, dataclasses.replace(
        base, coalesce=True, prefetch=True))
    assert l0 == l1 == l2
    for k in g0:
        assert (np.asarray(g0[k]) == np.asarray(g1[k])).all(), k
        assert (np.asarray(g1[k]) == np.asarray(g2[k])).all(), k


def test_engine_prefetch_moe_aux_carry(mesh11):
    """The pipelined scan must thread the (x, aux) MoE carry correctly."""
    mcfg = ModelConfig(name="tm", arch_type="moe", n_layers=2, d_model=64,
                       vocab_size=256, n_heads=4, n_kv_heads=4, head_dim=16,
                       n_experts=4, moe_top_k=2, moe_d_ff=64)
    base = QSDPConfig(min_quant_size=128, coalesce=True)
    l0, g0 = _loss_and_grads(mesh11, base, mcfg)
    l1, g1 = _loss_and_grads(mesh11, dataclasses.replace(base, prefetch=True),
                             mcfg)
    assert l0 == l1
    for k in g0:
        assert (np.asarray(g0[k]) == np.asarray(g1[k])).all(), k


def test_layer_gather_launches_analytic(mesh11):
    model = Model(MCFG, MS11, QSDPConfig(min_quant_size=128, coalesce=False))
    names = [n for n in model.specs if n.startswith("layers/")]
    # 7 quantized matmul weights x 3 launches + 2 fp norms x 1
    assert layer_gather_launches(model.engine, names) == 23
    model_co = Model(MCFG, MS11, QSDPConfig(min_quant_size=128, coalesce=True))
    assert layer_gather_launches(model_co.engine, names) == 1


# ---------------------------------------------------------------------------
# decode_attend irregular GQA (n_kv > n_heads) — the sanity_serve fix
# ---------------------------------------------------------------------------


def test_decode_attend_irregular_gqa(mesh11, key):
    from repro.models.attention import AttnConfig, decode_attend

    b, n_heads, n_kv, hd, s = 2, 8, 16, 16, 8
    cfg = AttnConfig(n_heads=n_heads, n_kv=n_kv, head_dim=hd, tp=1)
    assert cfg.group == 1 and cfg.n_heads != cfg.n_kv * cfg.group
    q = jax.random.normal(key, (b, n_heads, hd))
    kc = jax.random.normal(jax.random.fold_in(key, 1), (b, s, n_kv, hd),
                           dtype=jnp.bfloat16)
    vc = jax.random.normal(jax.random.fold_in(key, 2), (b, s, n_kv, hd),
                           dtype=jnp.bfloat16)

    @partial(shard_map, mesh=mesh11, in_specs=(P(), P(), P(), P()),
             out_specs=P(), check_vma=False)
    def f(q, kc, vc, pos):
        return decode_attend(q, kc, vc, cfg, pos[0], s)

    out = jax.jit(f)(q, kc, vc, jnp.full((1,), s - 1, jnp.int32))

    # reference: head j attends kv head clip(j // group) densely
    kv_idx = np.clip(np.arange(n_heads) // cfg.group, 0, n_kv - 1)
    qn, kn, vn = map(lambda a: np.asarray(a, np.float32), (q, kc, vc))
    for j in range(n_heads):
        sc = np.einsum("bd,bsd->bs", qn[:, j], kn[:, :, kv_idx[j]]) / np.sqrt(hd)
        w = np.exp(sc - sc.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        ref = np.einsum("bs,bsd->bd", w, vn[:, :, kv_idx[j]])
        np.testing.assert_allclose(np.asarray(out)[:, j], ref, atol=2e-2)


def test_decode_attend_regular_gqa_unchanged(mesh11, key):
    from repro.models.attention import AttnConfig, decode_attend

    b, n_heads, n_kv, hd, s = 2, 8, 2, 16, 8
    cfg = AttnConfig(n_heads=n_heads, n_kv=n_kv, head_dim=hd, tp=1)
    assert cfg.n_heads == cfg.n_kv * cfg.group
    q = jax.random.normal(key, (b, n_heads, hd))
    kc = jax.random.normal(jax.random.fold_in(key, 1), (b, s, n_kv, hd))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (b, s, n_kv, hd))

    @partial(shard_map, mesh=mesh11, in_specs=(P(), P(), P(), P()),
             out_specs=P(), check_vma=False)
    def f(q, kc, vc, pos):
        return decode_attend(q, kc, vc, cfg, pos[0], s)

    out = jax.jit(f)(q, kc, vc, jnp.full((1,), s - 1, jnp.int32))
    assert out.shape == (b, n_heads, hd)
    assert bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------------------
# analyzer: per-dtype launch counts
# ---------------------------------------------------------------------------


def test_analyzer_counts_by_dtype():
    from repro.roofline.hlo_analyzer import analyze_hlo
    from test_roofline import SYNTH

    r = analyze_hlo(SYNTH)
    d = r["collectives"]["counts_by_dtype"]
    assert d["all-gather:f32"] == 10  # in the x10 while body
    assert d["all-reduce:f32"] == 1
