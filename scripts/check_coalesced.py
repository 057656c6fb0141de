"""Coalesced-wire-format correctness + HLO launch-count regression checks
(run under 8 emulated devices).  Invoked by tests/test_distributed.py.

Validates:
  1. collective level, (8,) mesh: all_gather_coalesced / reduce_scatter_
     coalesced are BIT-EXACT vs. the per-tensor quantized collectives for
     bits {2,3,4,8} x all 3 rounding modes x both backends (same keys,
     same wire bytes, one launch), incl. mixed quantized+fp layouts.
  2. hierarchical variants on a (2,2,2) pod mesh: bit-exact vs. per-tensor.
  3. meta_wire_dtype="bfloat16": coalesced == per-tensor bit-exact, and
     close (~2^-8) to the f32-metadata decode.
  4. engine level, (2,4) mesh: loss and grads of a dense model with
     coalesce=True match coalesce=False — quantized-param grads bit-exact,
     fp (filtered) grads within bf16-wire tolerance.
  5. prefetch=True (double-buffered pipeline): loss and ALL grads bit-exact
     vs. the non-pipelined coalesced schedule.
  6. HLO regression (the acceptance criterion): per-layer marginal
     all-gather launch count of the compiled forward is 3*n_quant + n_fp
     per-tensor and exactly 1 (u8) coalesced — measured via
     roofline.hlo_analyzer counts on two stack depths.

Exit code 0 + 'ALL-OK' on success.
"""
from _mesh_common import FAIL, check, finish, force_host_devices

force_host_devices(8)
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import collectives as coll
from repro.core.qsdp import MeshSpec, QSDPConfig
from repro.core.quant import QuantConfig
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig
from repro.models.transformer import Model
from repro.roofline.hlo_analyzer import analyze_hlo

# ---------------------------------------------------------------------------
# 1. collective-level bit-exactness, (8,) mesh
# ---------------------------------------------------------------------------
mesh8 = make_mesh((8,), ("data",))
N = 2048  # per-device shard elements (not a bucket multiple for bits=3 path)


def ag_both(cfg):
    @partial(shard_map, mesh=mesh8, in_specs=(P("data"), P("data"), P()),
             out_specs=P("data"), check_vma=False)
    def f(xs, ys, key):
        x, y = xs.reshape(-1), ys.reshape(-1)
        ref_x = coll.all_gather_quantized(x, ("data",), cfg, key[0])
        ref_y = coll.all_gather_fp(y, ("data",))
        layout = coll.WireLayout((coll.WireSegment(x.shape[0], cfg),
                                  coll.WireSegment(y.shape[0], None, "float32")))
        co_x, co_y = coll.all_gather_coalesced(
            [x, y], ("data",), layout, [key[0], None],
            [jnp.float32, jnp.float32])
        return jnp.stack([jnp.concatenate([ref_x, ref_y]),
                          jnp.concatenate([co_x, co_y])])[None]

    x = jax.random.normal(jax.random.PRNGKey(0), (8, N))
    y = jax.random.normal(jax.random.PRNGKey(1), (8, 160))
    out = jax.jit(f)(x, y, jax.random.PRNGKey(2)[None])
    return out[0]


for bits in (2, 3, 4, 8):
    for mode in ("shift", "stochastic", "nearest"):
        for backend in ("jnp", "pallas"):
            cfg = QuantConfig(bits=bits, bucket_size=256, mode=mode, backend=backend)
            r = ag_both(cfg)
            check(f"ag-coalesced-bitexact-b{bits}-{mode}-{backend}",
                  bool(jnp.all(r[0] == r[1])),
                  f"maxdiff={float(jnp.max(jnp.abs(r[0] - r[1]))):.2e}")


def rs_both(cfg):
    from repro.core.quant import Quantized, dequantize, quantize, wire_pack, wire_unpack

    @partial(shard_map, mesh=mesh8, in_specs=(P("data"), P("data"), P()),
             out_specs=P("data"), check_vma=False)
    def f(gs, hs, key):
        g, h = gs.reshape(-1), hs.reshape(-1)
        p, n = 8, g.shape[0]
        ref_g = coll.reduce_scatter_quantized(g, ("data",), cfg, key[0])
        layout = coll.WireLayout((coll.WireSegment(n // p, cfg),
                                  coll.WireSegment(h.shape[0] // p, None, "bfloat16")))
        co_g, co_h = coll.reduce_scatter_coalesced([g, h], ("data",), layout,
                                                   [key[0], None])
        # fp reference: ship bf16 chunks, sum in f32 (the coalesced contract)
        ref_h = jnp.sum(
            jax.vmap(lambda c: coll.fp_unpack(coll.fp_pack(c, "bfloat16"),
                                              h.shape[0] // p, "bfloat16"))(
                jax.lax.all_to_all(h.reshape(p, -1), ("data",), 0, 0, tiled=True)),
            axis=0)
        # per-chunk DECODE bit-exactness: per-tensor collectives vs the wire
        # round-trip, same exchanged bytes, before any reduction
        q = jax.vmap(lambda c, k: quantize(c, cfg, k))(
            g.reshape(p, n // p), jax.random.split(key[0], p))
        codes = jax.lax.all_to_all(q.codes, ("data",), 0, 0, tiled=True)
        scale = jax.lax.all_to_all(q.scale, ("data",), 0, 0, tiled=True)
        zero = jax.lax.all_to_all(q.zero, ("data",), 0, 0, tiled=True)
        deq_ref = jax.vmap(lambda c, s, z: dequantize(
            Quantized(c, s, z, (n // p,), n // p, cfg)))(codes, scale, zero)
        rbuf = jax.lax.all_to_all(jax.vmap(wire_pack)(q), ("data",), 0, 0, tiled=True)
        deq_co = jax.vmap(lambda b: dequantize(wire_unpack(b, n // p, cfg)))(rbuf)
        decode_diff = jnp.max(jnp.abs(deq_ref - deq_co)) * jnp.ones_like(ref_g)
        return jnp.stack([jnp.concatenate([ref_g, ref_h]),
                          jnp.concatenate([co_g, co_h]),
                          jnp.concatenate([decode_diff, jnp.zeros_like(ref_h)])])[None]

    g = jax.random.normal(jax.random.PRNGKey(3), (8, N * 8))
    h = jax.random.normal(jax.random.PRNGKey(4), (8, 512))
    out = jax.jit(f)(g, h, jax.random.PRNGKey(5)[None])
    return out


for bits in (2, 4, 8):
    for mode in ("stochastic", "nearest"):
        cfg = QuantConfig(bits=bits, bucket_size=256, mode=mode)
        out = rs_both(cfg)
        check(f"rs-coalesced-decode-bitexact-b{bits}-{mode}",
              float(jnp.max(out[:, 2])) == 0.0,
              f"decode maxdiff={float(jnp.max(out[:, 2])):.2e}")
        # the summed RS result may differ by float reassociation only (XLA
        # fuses decode->sum differently across the two lowerings): ~1 ulp
        # at the summand scale, NOT a wire/decode discrepancy
        sum_diff = float(jnp.max(jnp.abs(out[:, 0] - out[:, 1])))
        check(f"rs-coalesced-sum-b{bits}-{mode}", sum_diff < 1e-5,
              f"maxdiff={sum_diff:.2e}")

# ---------------------------------------------------------------------------
# 2. hierarchical coalesced == per-tensor hierarchical, (2,2,2) mesh
# ---------------------------------------------------------------------------
mesh_pod = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfgh = QuantConfig(bits=8, bucket_size=256, mode="shift")


@partial(shard_map, mesh=mesh_pod, in_specs=(P(("data", "pod")), P()),
         out_specs=P(("data", "pod")), check_vma=False)
def hier_both(xs, key):
    x = xs.reshape(-1)
    ref = coll.all_gather_hierarchical(x, "pod", ("data",), cfgh, key[0])
    layout = coll.WireLayout((coll.WireSegment(x.shape[0], cfgh),))
    (co,) = coll.all_gather_coalesced([x], ("data", "pod"), layout, [key[0]],
                                      [jnp.float32], pod_axis="pod")
    rs_ref = coll.reduce_scatter_hierarchical(x, "pod", ("data",), cfgh, key[0])
    l1 = coll.WireLayout((coll.WireSegment(x.shape[0] // 2, cfgh),))
    l2 = coll.WireLayout((coll.WireSegment(x.shape[0] // 4, cfgh),))
    (rs_co,) = coll.reduce_scatter_coalesced_hierarchical(
        [x], "pod", ("data",), l1, l2, [key[0]])
    pad = jnp.zeros(ref.shape[0] - rs_ref.shape[0], jnp.float32)
    return jnp.stack([ref, co, jnp.concatenate([rs_ref, pad]),
                      jnp.concatenate([rs_co, pad])])[None]


xh = jax.random.normal(jax.random.PRNGKey(6), (4, 512))
out = jax.jit(hier_both)(xh, jax.random.PRNGKey(7)[None])
check("hier-ag-coalesced-bitexact", bool(jnp.all(out[:, 0] == out[:, 1])))
check("hier-rs-coalesced-bitexact", bool(jnp.all(out[:, 2] == out[:, 3])))

# ---------------------------------------------------------------------------
# 3. bf16 metadata wire
# ---------------------------------------------------------------------------
cfg16 = QuantConfig(bits=8, bucket_size=256, mode="shift", meta_dtype="bfloat16")
r16 = ag_both(cfg16)
check("ag-coalesced-bitexact-bf16meta", bool(jnp.all(r16[0] == r16[1])))
cfg32 = dataclasses.replace(cfg16, meta_dtype="float32")
r32 = ag_both(cfg32)
rel = float(jnp.max(jnp.abs(r16[0] - r32[0])) / (jnp.max(jnp.abs(r32[0])) + 1e-9))
check("bf16meta-close-to-f32meta", 0 < rel < 0.02, f"rel={rel:.2e}")
b16 = coll.gather_wire_bytes(N, 8, cfg16)
b32 = coll.gather_wire_bytes(N, 8, cfg32)
check("bf16meta-fewer-wire-bytes", b16 == b32 - 7 * 2 * 2 * (N // 256),
      f"{b16} vs {b32}")

# ---------------------------------------------------------------------------
# 4-5. engine level: coalesce / prefetch vs per-tensor, (2,4) mesh
# ---------------------------------------------------------------------------
mesh24 = make_mesh((2, 4), ("data", "model"))
ms = MeshSpec(axes=("data", "model"), shape=(2, 4))
mcfg = ModelConfig(name="t", arch_type="dense", n_layers=3, d_model=128,
                   vocab_size=256, n_heads=8, n_kv_heads=4, head_dim=16, d_ff=256)


def loss_and_grads(qcfg):
    model = Model(mcfg, ms, qcfg)
    params = model.init_params(jax.random.PRNGKey(20))

    @partial(shard_map, mesh=mesh24,
             in_specs=(model.param_pspecs(), {"tokens": P(("data",)), "labels": P(("data",))}, P()),
             out_specs=(P(), model.param_pspecs()), check_vma=False)
    def f(p, b, k):
        loss, g = jax.value_and_grad(model.loss_fn)(p, b, k)
        return jax.lax.pmean(loss, ("data", "model")), g

    tokens = jax.random.randint(jax.random.PRNGKey(21), (4, 16), 0, 256)
    batch = {"tokens": tokens, "labels": tokens}
    loss, g = jax.jit(f)(params, batch, jax.random.PRNGKey(22))
    return model, float(loss), jax.device_get(g)


q_base = QSDPConfig(min_quant_size=256, coalesce=False)
q_co = dataclasses.replace(q_base, coalesce=True)
q_pf = dataclasses.replace(q_base, coalesce=True, prefetch=True)

model, l0, g0 = loss_and_grads(q_base)
_, l1, g1 = loss_and_grads(q_co)
_, l2, g2 = loss_and_grads(q_pf)

check("engine-coalesce-loss-bitexact", l0 == l1, f"{l0} vs {l1}")
check("engine-prefetch-loss-bitexact", l1 == l2, f"{l1} vs {l2}")

worst_fp, ok_q = 0.0, True
for k in g0:
    spec = model.specs[k]
    if model.engine._is_grad_quantized(spec):
        ok_q &= bool((np.asarray(g0[k]) == np.asarray(g1[k])).all())
    else:
        d = float(np.max(np.abs(np.asarray(g0[k]) - np.asarray(g1[k]))))
        s = float(np.max(np.abs(np.asarray(g0[k]))) + 1e-9)
        worst_fp = max(worst_fp, d / s)
check("engine-coalesce-quantgrads-bitexact", ok_q)
check("engine-coalesce-fpgrads-close", worst_fp < 2e-2, f"rel={worst_fp:.2e}")

ok_pf = all(bool((np.asarray(g1[k]) == np.asarray(g2[k])).all()) for k in g1)
check("engine-prefetch-grads-bitexact", ok_pf)

# ---------------------------------------------------------------------------
# 6. HLO launch-count regression: 3*n_quant + n_fp -> 1 per layer gather
# ---------------------------------------------------------------------------


def fwd_ag_counts(qcfg, n_layers):
    c = dataclasses.replace(mcfg, n_layers=n_layers)
    model = Model(c, ms, qcfg)
    params = model.init_params(jax.random.PRNGKey(30))

    @partial(shard_map, mesh=mesh24,
             in_specs=(model.param_pspecs(), {"tokens": P(("data",)), "labels": P(("data",))}, P()),
             out_specs=P(), check_vma=False)
    def f(p, b, k):
        return jax.lax.pmean(model.loss_fn(p, b, k), ("data", "model"))

    tokens = jnp.zeros((4, 16), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    hlo = jax.jit(f).lower(params, batch, jax.random.PRNGKey(31)).compile().as_text()
    r = analyze_hlo(hlo)
    return r["collectives"]["counts"], r["collectives"]["counts_by_dtype"]


# layer params: 7 quantized (wq wk wv wo w_gate w_up w_down) + 2 fp norms
c2, _ = fwd_ag_counts(q_base, 2)
c4, _ = fwd_ag_counts(q_base, 4)
marg_base = (c4["all-gather"] - c2["all-gather"]) / 2
check("hlo-per-tensor-marginal-23", marg_base == 3 * 7 + 2,
      f"marginal={marg_base}")

c2, d2 = fwd_ag_counts(q_co, 2)
c4, d4 = fwd_ag_counts(q_co, 4)
marg_co = (c4["all-gather"] - c2["all-gather"]) / 2
marg_u8 = (d4.get("all-gather:u8", 0) - d2.get("all-gather:u8", 0)) / 2
check("hlo-coalesced-marginal-1", marg_co == 1, f"marginal={marg_co}")
check("hlo-coalesced-marginal-is-u8", marg_u8 == 1, f"marginal={marg_u8}")

c2, d2 = fwd_ag_counts(q_pf, 2)
c4, d4 = fwd_ag_counts(q_pf, 4)
marg_pf = (d4.get("all-gather:u8", 0) - d2.get("all-gather:u8", 0)) / 2
check("hlo-prefetch-marginal-1", marg_pf == 1, f"marginal={marg_pf}")

finish()
