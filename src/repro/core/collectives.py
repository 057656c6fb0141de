"""Quantized collectives — the TPU-native equivalents of CGX's quantized
NCCL AllGather / ReduceScatter (paper Section 5).

All functions here are *per-device* code: they must be called inside
``jax.shard_map``.  Axis names refer to mesh axes of the enclosing
shard_map.

Design notes (see DESIGN.md §2):

* **Quantized all-gather** ships int8-packed codes + per-bucket (scale, zero)
  metadata (f32, or bf16 under ``QuantConfig.meta_dtype="bfloat16"``).  The
  receiving side dequantizes after the gather, so the wire carries
  ``~ bits/32`` of the fp32 volume.  Appears in compiled HLO as
  ``all-gather`` of ``u8[...]`` operands — this is what the roofline parser
  counts.

* **Coalesced wire format** (the per-*launch* optimization): the per-tensor
  collectives above still cost 3 launches per tensor (codes, scale, zero) —
  a transformer layer with 7 quantized params is 21+ all-gather launches.
  The ``*_coalesced`` variants serialize every tensor of a layer — packed
  codes + metadata for quantized params, bitcast fp payloads for filtered
  ones — into ONE contiguous u8 buffer (``core.quant.wire_pack``) and issue
  ONE collective per layer, with bit-exact decode on the receiving side
  (same per-tensor quantization keys, same bytes on the wire, just one
  launch).  ``WireLayout`` is the static description of that buffer.

* **Quantized reduce-scatter** cannot use a ring reduce-scatter (codes from
  different peers have different scales and cannot be summed in transit).
  The TPU-native formulation is a single ``all_to_all`` of quantized chunks
  followed by a local dequant-sum: identical wire volume to a ring RS
  (``(P-1)/P * N * bits/8`` per device) and one collective instead of P-1
  steps.  This mirrors how CGX implements it over NCCL P2P.  The coalesced
  variant ships all of a layer's per-destination chunk rows in one
  ``(P, layer_bytes)`` u8 all_to_all.

* **Hierarchical variants** split the FSDP axes (pod, data): reduce-scatter
  over the fast in-pod axis first, so only ``1/data`` of the volume crosses
  the slow pod boundary — the paper's hierarchical inter-node collectives.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from jax.lax import axis_size
from .quant import (
    QuantConfig,
    Quantized,
    dequantize,
    fp_pack,
    fp_segment_bytes,
    fp_unpack,
    quantize,
    quantized_shapes,
    wire_pack,
    wire_segment_bytes,
    wire_unpack,
)

AxisNames = tuple[str, ...]


def _axis_size(axes: AxisNames) -> int:
    s = 1
    for a in axes:
        s *= axis_size(a)  # static int
    return s


# ---------------------------------------------------------------------------
# Full-precision fallbacks (filtered params / baseline FSDP)
# ---------------------------------------------------------------------------


def all_gather_fp(x: jax.Array, axes: AxisNames, dtype=None) -> jax.Array:
    """Plain all-gather, optionally casting the wire dtype (baseline FSDP
    ships weights fp32, i.e. dtype=None; bf16 wire is a cheap ablation)."""
    if dtype is not None and x.dtype != dtype:
        y = lax.all_gather(x.astype(dtype), axes, tiled=True)
        return y.astype(x.dtype)
    return lax.all_gather(x, axes, tiled=True)


def reduce_scatter_fp(x: jax.Array, axes: AxisNames, dtype=None) -> jax.Array:
    """Plain reduce-scatter (sum) over flattened leading dim."""
    if dtype is not None and x.dtype != dtype:
        return lax.psum_scatter(x.astype(dtype), axes, tiled=True).astype(x.dtype)
    return lax.psum_scatter(x, axes, tiled=True)


# ---------------------------------------------------------------------------
# Quantized all-gather
# ---------------------------------------------------------------------------


def _decode_shards(
    codes: jax.Array,
    scale: jax.Array,
    zero: jax.Array,
    p: int,
    n_local: int,
    cfg: QuantConfig,
    dtype,
) -> jax.Array:
    """Decode P concatenated per-shard code blocks, respecting the fact that
    each source shard was padded to a bucket multiple *independently*."""
    nb_local = codes.shape[0] // p
    chunks = codes.reshape(p, nb_local, codes.shape[-1])
    out = jax.vmap(
        lambda c, s, z: dequantize(Quantized(c, s, z, (n_local,), n_local, cfg))
    )(chunks, scale.reshape(p, nb_local), zero.reshape(p, nb_local))
    return out.reshape(-1).astype(dtype)


def all_gather_quantized(
    x: jax.Array, axes: AxisNames, cfg: QuantConfig, key: jax.Array,
    out_dtype=None,
) -> jax.Array:
    """Gather a flat per-device shard into the full (flat) tensor, shipping
    quantized codes.  x: (n_local,) f32/bf16 -> (P * n_local,) out_dtype
    (default x.dtype).  Decoding straight to bf16 halves the materialized
    weight bytes with zero information loss (codes are <=8 bits) — §Perf."""
    q = quantize(x, cfg, key)
    md = cfg.meta_jnp_dtype
    codes = lax.all_gather(q.codes, axes, tiled=True)  # (P*nb, bsz/cpb) u8
    scale = lax.all_gather(q.scale.astype(md), axes, tiled=True)  # (P*nb,)
    zero = lax.all_gather(q.zero.astype(md), axes, tiled=True)
    p = _axis_size(axes)
    return _decode_shards(codes, scale.astype(jnp.float32),
                          zero.astype(jnp.float32), p, x.shape[0], cfg,
                          out_dtype or x.dtype)


def all_gather_hierarchical(
    x: jax.Array, pod_axis: str, inner_axes: AxisNames, cfg: QuantConfig,
    key: jax.Array, out_dtype=None,
) -> jax.Array:
    """Two-level gather: cross-pod first (moves only the local shard over the
    slow links), then in-pod.  Because the engine orders its flat FSDP axes
    data-major (`fsdp_axes = ("data", "pod")`), gathering over "pod" first and
    then "data" reproduces exactly the flat element order."""
    q = quantize(x, cfg, key)
    md = cfg.meta_jnp_dtype
    codes = lax.all_gather(q.codes, pod_axis, tiled=True)
    scale = lax.all_gather(q.scale.astype(md), pod_axis, tiled=True)
    zero = lax.all_gather(q.zero.astype(md), pod_axis, tiled=True)
    codes = lax.all_gather(codes, inner_axes, tiled=True)
    scale = lax.all_gather(scale, inner_axes, tiled=True)
    zero = lax.all_gather(zero, inner_axes, tiled=True)
    p = axis_size(pod_axis) * _axis_size(inner_axes)
    return _decode_shards(codes, scale.astype(jnp.float32),
                          zero.astype(jnp.float32), p, x.shape[0], cfg,
                          out_dtype or x.dtype)


# ---------------------------------------------------------------------------
# Quantized reduce-scatter (sum) via all_to_all + local dequant-sum
# ---------------------------------------------------------------------------


def reduce_scatter_quantized(
    g: jax.Array, axes: AxisNames, cfg: QuantConfig, key: jax.Array
) -> jax.Array:
    """Sum `g` across `axes`, leaving each device its own 1/P chunk.

    g: (n,) per-device full (unreduced) tensor with n % P == 0.
    Returns (n/P,) f32 — the summed chunk owned by this device.
    """
    p = _axis_size(axes)
    n = g.shape[0]
    assert n % p == 0, (n, p)
    chunks = g.reshape(p, n // p)
    q = jax.vmap(lambda c, k: quantize(c, cfg, k))(
        chunks, jax.random.split(key, p)
    )
    md = cfg.meta_jnp_dtype
    # Each row i goes to device i of the logical axis; we receive P rows.
    codes = lax.all_to_all(q.codes, axes, split_axis=0, concat_axis=0, tiled=True)
    scale = lax.all_to_all(q.scale.astype(md), axes, split_axis=0, concat_axis=0, tiled=True)
    zero = lax.all_to_all(q.zero.astype(md), axes, split_axis=0, concat_axis=0, tiled=True)
    deq = jax.vmap(
        lambda c, s, z: dequantize(
            Quantized(c, s, z, (n // p,), n // p, cfg)
        )
    )(codes, scale.astype(jnp.float32), zero.astype(jnp.float32))
    return jnp.sum(deq, axis=0)


def reduce_scatter_hierarchical(
    g: jax.Array, pod_axis: str, inner_axes: AxisNames, cfg: QuantConfig, key: jax.Array
) -> jax.Array:
    """Two-level quantized reduce-scatter: RS over the in-pod axes first
    (full volume stays on fast links), then RS of the 1/inner-sized partial
    across pods — only ``n/inner`` bytes cross the pod boundary."""
    k1, k2 = jax.random.split(key)
    partial_sum = reduce_scatter_quantized(g, inner_axes, cfg, k1)
    return reduce_scatter_quantized(partial_sum, (pod_axis,), cfg, k2)


# ---------------------------------------------------------------------------
# Coalesced wire collectives: one launch per layer.
#
# ``WireLayout`` statically describes the concatenation of every tensor of a
# layer into one u8 buffer (see the module docstring).  ``encode_wire`` /
# ``gather_wire`` / ``decode_gathered_wire`` are split so the QSDP engine can
# issue the collective for layer i+1 while layer i computes (the
# double-buffered prefetch pipeline) and decode the carried buffer one scan
# step later.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WireSegment:
    """Static layout of one tensor inside a coalesced wire buffer.

    n:        flat element count contributed per device (shard or chunk)
    cfg:      quantization config, or None for a raw fp payload
    fp_dtype: wire dtype of the fp payload when cfg is None
    """

    n: int
    cfg: Optional[QuantConfig]
    fp_dtype: str = "float32"

    @property
    def nbytes(self) -> int:
        if self.cfg is None:
            return fp_segment_bytes(self.n, self.fp_dtype)
        return wire_segment_bytes(self.n, self.cfg)


@dataclasses.dataclass(frozen=True)
class WireLayout:
    """Static layout of a whole coalesced buffer (ordered segments)."""

    segments: tuple[WireSegment, ...]

    @property
    def nbytes(self) -> int:
        return sum(s.nbytes for s in self.segments)

    def offsets(self) -> list[int]:
        out, off = [], 0
        for s in self.segments:
            out.append(off)
            off += s.nbytes
        return out


def encode_wire(xs: Sequence[jax.Array], layout: WireLayout,
                keys: Sequence[Optional[jax.Array]]) -> jax.Array:
    """Quantize + serialize every tensor into one (layout.nbytes,) u8 buffer.
    Quantized segments draw randomness from their own per-tensor key, so the
    bytes are identical to what the per-tensor collectives would ship."""
    parts = []
    for x, seg, key in zip(xs, layout.segments, keys):
        flat = x.reshape(-1)
        if seg.cfg is None:
            parts.append(fp_pack(flat, seg.fp_dtype))
        else:
            parts.append(wire_pack(quantize(flat, seg.cfg, key)))
    return jnp.concatenate(parts)


def gather_wire(buf: jax.Array, axes: AxisNames,
                pod_axis: Optional[str] = None) -> jax.Array:
    """All-gather a coalesced buffer: (B,) u8 -> (P*B,) u8 in shard order.
    With `pod_axis`, gathers cross-pod first (hierarchical two-level form —
    same peer ordering as the per-tensor hierarchical gather)."""
    if pod_axis is not None:
        buf = lax.all_gather(buf, pod_axis, tiled=True)
        inner = tuple(a for a in axes if a != pod_axis)
        return lax.all_gather(buf, inner, tiled=True)
    return lax.all_gather(buf, axes, tiled=True)


def _decode_segments(rows: jax.Array, layout: WireLayout) -> list[jax.Array]:
    """(P, layout.nbytes) u8 rows -> per-segment (P, seg.n) f32 decodes
    (shared by the gather decode and the reduce-scatter dequant-sum)."""
    outs, off = [], 0
    for seg in layout.segments:
        sb = rows[:, off:off + seg.nbytes]
        off += seg.nbytes
        if seg.cfg is None:
            outs.append(jax.vmap(lambda b: fp_unpack(b, seg.n, seg.fp_dtype))(sb))
        else:
            outs.append(jax.vmap(
                lambda b: dequantize(wire_unpack(b, seg.n, seg.cfg))
            )(sb))
    return outs


def decode_gathered_wire(gbuf: jax.Array, layout: WireLayout, p: int,
                         out_dtypes: Sequence) -> list[jax.Array]:
    """Decode a gathered (P * layout.nbytes,) buffer back into full flat
    tensors [(P * seg.n,) in out_dtype], respecting per-shard padding."""
    rows = gbuf.reshape(p, layout.nbytes)
    return [vals.reshape(-1).astype(dt)
            for vals, dt in zip(_decode_segments(rows, layout), out_dtypes)]


def all_gather_coalesced(
    xs: Sequence[jax.Array], axes: AxisNames, layout: WireLayout,
    keys: Sequence[Optional[jax.Array]], out_dtypes: Sequence,
    pod_axis: Optional[str] = None,
) -> list[jax.Array]:
    """One-launch layer gather: encode -> 1 all-gather -> decode."""
    buf = encode_wire(xs, layout, keys)
    gbuf = gather_wire(buf, axes, pod_axis=pod_axis)
    p = _axis_size(axes)
    return decode_gathered_wire(gbuf, layout, p, out_dtypes)


def reduce_scatter_coalesced(
    gs: Sequence[jax.Array], axes: AxisNames, layout: WireLayout,
    keys: Sequence[Optional[jax.Array]],
) -> list[jax.Array]:
    """One-launch layer reduce-scatter (sum): each tensor's P destination
    chunks are quantized (or bitcast, for fp segments) into per-destination
    byte rows; all tensors' rows ride ONE (P, layout.nbytes) u8 all_to_all,
    then each destination dequant-sums its P received chunks.

    layout.segments[i].n must equal gs[i].size // P.  Quantized segments are
    bit-identical on the wire to `reduce_scatter_quantized` with the same
    key; fp segments ship grad_wire_dtype bytes but are summed in f32 after
    the exchange (the ring psum_scatter reduces in the wire dtype instead —
    the coalesced form is at least as accurate)."""
    p = _axis_size(axes)
    rows = []
    for g, seg, key in zip(gs, layout.segments, keys):
        chunks = g.reshape(p, seg.n)
        if seg.cfg is None:
            rows.append(jax.vmap(lambda c: fp_pack(c, seg.fp_dtype))(chunks))
        else:
            q = jax.vmap(lambda c, k: quantize(c, seg.cfg, k))(
                chunks, jax.random.split(key, p))
            rows.append(jax.vmap(wire_pack)(q))
    buf = jnp.concatenate(rows, axis=1)  # (P, layout.nbytes)
    rbuf = lax.all_to_all(buf, axes, split_axis=0, concat_axis=0, tiled=True)
    return [jnp.sum(deq, axis=0) for deq in _decode_segments(rbuf, layout)]


def reduce_scatter_coalesced_hierarchical(
    gs: Sequence[jax.Array], pod_axis: str, inner_axes: AxisNames,
    inner_layout: WireLayout, pod_layout: WireLayout,
    keys: Sequence[Optional[jax.Array]],
) -> list[jax.Array]:
    """Two-level coalesced RS: full volume over the fast in-pod axes, then
    the 1/inner-sized partial across pods (one launch per level per layer).
    Per-tensor keys are split exactly like `reduce_scatter_hierarchical`."""
    k1 = [None if k is None else jax.random.split(k)[0] for k in keys]
    k2 = [None if k is None else jax.random.split(k)[1] for k in keys]
    partial_sums = reduce_scatter_coalesced(gs, inner_axes, inner_layout, k1)
    return reduce_scatter_coalesced(partial_sums, (pod_axis,), pod_layout, k2)


# ---------------------------------------------------------------------------
# Wire-byte accounting (used by the analytic communication model)
# ---------------------------------------------------------------------------


def gather_wire_bytes(n_local: int, p: int, cfg: QuantConfig | None, fp_bytes: int = 4) -> int:
    """Per-device bytes moved by one all-gather of an n_local-element shard
    (ring: receive (P-1) shards).  Identical for the per-tensor and the
    coalesced wire format — coalescing changes launches, not bytes."""
    if cfg is None:
        return (p - 1) * n_local * fp_bytes
    s = quantized_shapes(n_local, cfg)
    per_shard = s["codes"][0] * s["codes"][1] + 2 * cfg.meta_bytes * s["scale"][0]
    return (p - 1) * per_shard


def reduce_scatter_wire_bytes(n: int, p: int, cfg: QuantConfig | None, fp_bytes: int = 4) -> int:
    """Per-device bytes moved by one reduce-scatter of an n-element tensor."""
    if cfg is None:
        return (p - 1) * (n // p) * fp_bytes
    s = quantized_shapes(n // p, cfg)
    per_chunk = s["codes"][0] * s["codes"][1] + 2 * cfg.meta_bytes * s["scale"][0]
    return (p - 1) * per_chunk
