"""Pallas TPU kernels for bucketed quantize / dequantize.

These are QSDP's compute hot-spots: every all-gather quantizes the local
shard and every receiver dequantizes P shards, at every layer, twice per
step (fwd + bwd re-gather) plus once for the gradient reduce-scatter.  On
GPU the paper implements these inside CGX as CUDA kernels; here they are
TPU-native Pallas kernels:

  * the bucket axis (1024 values) is the 128-lane minor dimension times 8
    sublanes, i.e. one bucket == one full (8, 128) f32 VREG tile — min/max
    reductions over a bucket are intra-tile and cheap on the VPU;
  * a block of ROWS_PER_TILE buckets is staged in VMEM per grid step;
  * randomness (stochastic-rounding uniforms, per-bucket random shifts)
    enters as a pre-generated array drawn from the SAME PRNG stream as the
    jnp reference in ``core.quant``, so the two backends are bit-exact;
  * Mosaic (the TPU kernel compiler) casts neither f32 nor u8 to the other
    directly, so every u8 conversion goes through int32;
  * Mosaic has no lane-strided slice, so the k = 8/bits codes of a byte are
    packed by an exact MXU matmul: a 0/2^(i*bits) matrix maps each group of
    128*k codes (lanes) onto 128 packed bytes.  Codes are integers <= 255
    and the weights powers of two, so bf16 operands with f32 accumulation
    reproduce the integer sum bit-for-bit.

Wire format (must match ``core.quant`` exactly — it is what goes on the
wire in the quantized collectives):

  codes  u8 (nb, bucket_size * bits / 8)   bit-packed when 8 % bits == 0:
         byte j of a bucket holds codes ``j*k .. j*k+k-1`` (k = 8/bits),
         code ``j*k+i`` in bits ``[i*bits, (i+1)*bits)`` — little-endian
         within the byte, identical to ``core.quant.pack_codes``;
  scale  f32 (nb, 1)   per-bucket step ((max - min) / levels);
  zero   f32 (nb, 1)   per-bucket affine offset (min, plus the folded-in
         random shift for mode="shift").

Two kernel families live here:

  1. ``quantize_pallas`` / ``dequantize_pallas`` — the original unpacked
     kernels (one u8 byte per code), kept for 3/5/6/7-bit widths and as
     the simplest-possible reference kernels.
  2. ``quantize_pack_pallas`` / ``unpack_dequantize_pallas`` — **fused**
     quantize→bit-pack and bit-unpack→dequantize: sub-8-bit codes never
     materialize as one-byte-per-code intermediates in HBM; the pack
     matmul and the unpack shifts run over the VMEM-resident tile.  These implement
     all three rounding modes of the wire quantizer ("nearest",
     "stochastic", "shift") and are the kernels ``core.quant`` dispatches
     to (see the ``backend=`` / ``REPRO_QUANT_BACKEND`` /
     ``REPRO_PALLAS_INTERPRET`` knobs documented in ``kernels.ops``).

Validated in interpret mode on CPU against `ref.py` and ``core.quant``
(bit-exact for codes and packed wire bytes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS_PER_TILE = 8


def _to_u8(x: jax.Array) -> jax.Array:
    """Integer-valued f32/int32 -> u8 through int32 (Mosaic has no f32->u8)."""
    return x.astype(jnp.int32).astype(jnp.uint8)


def _from_u8(x: jax.Array) -> jax.Array:
    """u8 -> f32 through int32 (Mosaic has no u8->f32)."""
    return x.astype(jnp.int32).astype(jnp.float32)


def _quantize_kernel(levels: int, stochastic: bool, x_ref, rand_ref, codes_ref, scale_ref, zero_ref):
    x = x_ref[...]  # (R, bucket) f32
    lo = jnp.min(x, axis=1, keepdims=True)
    hi = jnp.max(x, axis=1, keepdims=True)
    scale = jnp.maximum((hi - lo) * (1.0 / levels), 1e-12)
    v = (x - lo) / scale
    if stochastic:
        f = jnp.floor(v)
        codes = f + (rand_ref[...] < (v - f)).astype(v.dtype)
    else:
        codes = jnp.round(v)
    codes_ref[...] = _to_u8(jnp.clip(codes, 0, levels))
    scale_ref[...] = scale
    zero_ref[...] = lo


def quantize_pallas(
    x: jax.Array,
    rand: jax.Array,
    levels: int,
    stochastic: bool,
    *,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x, rand: (nb, bucket) f32 with nb % ROWS_PER_TILE == 0 (pad upstream).

    Returns (codes u8 (nb, bucket), scale f32 (nb, 1), zero f32 (nb, 1)).
    """
    nb, bucket = x.shape
    assert nb % ROWS_PER_TILE == 0, nb
    grid = (nb // ROWS_PER_TILE,)
    kern = functools.partial(_quantize_kernel, levels, stochastic)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS_PER_TILE, bucket), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_TILE, bucket), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROWS_PER_TILE, bucket), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_TILE, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, bucket), jnp.uint8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, rand)


# ---------------------------------------------------------------------------
# Fused quantize -> bit-pack  (and bit-unpack -> dequantize below)
# ---------------------------------------------------------------------------

_MODES = ("nearest", "stochastic", "shift")


def _pack_k(bits: int) -> int:
    return 8 // bits if 8 % bits == 0 else 1


def _pack_group(bucket: int, k: int) -> int:
    """Codes per pack matmul: 128*k lanes in -> 128 packed lanes out, or the
    whole bucket when it is not a multiple of 128*k."""
    return 128 * k if bucket % (128 * k) == 0 else bucket


def _pack_matrix(group: int, bits: int) -> jax.Array:
    """(group, group/k) bf16 with 2^(i*bits) at row j*k+i, column j: a codes
    row times this matrix is the packed byte row of ``core.quant.pack_codes``."""
    k = _pack_k(bits)
    e = jnp.arange(group)[:, None]
    j = jnp.arange(group // k)[None, :]
    return jnp.where(e // k == j, 2.0 ** ((e % k) * bits), 0.0).astype(jnp.bfloat16)


def _quantize_pack_kernel(levels, bits, mode, rand_scale,
                          x_ref, rand_ref, pack_ref, codes_ref, scale_ref, zero_ref):
    """One (R, bucket) tile: bucketed min-max quantize with the selected
    rounding mode, then bit-pack k = 8/bits codes per byte.

    The arithmetic is kept expression-for-expression identical to the jnp
    reference path in ``core.quant.quantize`` so both backends produce the
    same wire bytes.
    """
    x = x_ref[...]  # (R, bucket) f32
    lo = jnp.min(x, axis=1, keepdims=True)
    hi = jnp.max(x, axis=1, keepdims=True)
    # `* (1/levels)` not `/ levels`: matches the jnp reference exactly in
    # both eager and jit (XLA rewrites constant divisions to reciprocal
    # multiplies under jit — see core.quant.quantize).
    scale = jnp.maximum((hi - lo) * (1.0 / levels), 1e-12)
    v = (x - lo) / scale
    if mode == "stochastic":
        f = jnp.floor(v)
        up = rand_ref[...] < (v - f) * rand_scale
        codes = f + up.astype(v.dtype)
        zero = lo
    elif mode == "shift":
        r = rand_ref[...]  # (R, 1) shared shift per bucket
        codes = jnp.round(v - r)
        zero = lo + r * scale  # fold the shift into the affine decode
    else:  # nearest
        codes = jnp.round(v)
        zero = lo
    codes = jnp.clip(codes, 0, levels)
    if _pack_k(bits) > 1:
        # byte j <- sum_i codes[j*k + i] << (i*bits), one exact matmul per
        # lane group (see the module docstring).
        group, out_group = pack_ref.shape
        codes = codes.astype(jnp.bfloat16)
        for g in range(x.shape[1] // group):
            packed = jnp.dot(codes[:, g * group:(g + 1) * group], pack_ref[...],
                             preferred_element_type=jnp.float32)
            codes_ref[:, g * out_group:(g + 1) * out_group] = _to_u8(packed)
    else:
        codes_ref[...] = _to_u8(codes)
    scale_ref[...] = scale
    zero_ref[...] = zero


def quantize_pack_pallas(
    x: jax.Array,
    rand: jax.Array,
    levels: int,
    bits: int,
    mode: str = "nearest",
    rand_scale: float = 1.0,
    *,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused quantize→pack.  x: (nb, bucket) f32, nb % ROWS_PER_TILE == 0.

    rand: per-mode randomness, drawn upstream from the same PRNG stream as
    the jnp reference —
      * mode="stochastic": (nb, bucket) thresholds; ``up = rand < frac *
        rand_scale`` (rand_scale=1 for f32 uniforms, 65536 for u16 raw bits);
      * mode="shift": (nb, 1) per-bucket shifts in [-0.5, 0.5);
      * mode="nearest": unused, pass (nb, 1) zeros.

    Returns (packed codes u8 (nb, bucket*bits/8), scale (nb, 1), zero (nb, 1)).
    """
    assert mode in _MODES, mode
    nb, bucket = x.shape
    assert nb % ROWS_PER_TILE == 0, nb
    k = _pack_k(bits)
    assert bucket % k == 0, (bucket, k)
    n_packed = bucket // k
    grid = (nb // ROWS_PER_TILE,)
    rand_cols = rand.shape[1]
    if k > 1:
        pack = _pack_matrix(_pack_group(bucket, k), bits)
    else:  # nothing to pack; the kernel ignores this operand
        pack = jnp.zeros((1, 1), jnp.bfloat16)
    kern = functools.partial(_quantize_pack_kernel, levels, bits, mode, rand_scale)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS_PER_TILE, bucket), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_TILE, rand_cols), lambda i: (i, 0)),
            pl.BlockSpec(pack.shape, lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROWS_PER_TILE, n_packed), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_TILE, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, n_packed), jnp.uint8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, rand, pack)


def _unpack_dequantize_kernel(bits, out_dtype, codes_ref, scale_ref, zero_ref, out_ref):
    packed = codes_ref[...].astype(jnp.int32)  # (R, bucket*bits/8) u8 bytes
    k = _pack_k(bits)
    if k > 1:
        mask = (1 << bits) - 1
        r, nbytes = packed.shape
        # element j*k + i of a bucket lives in bits [i*bits, (i+1)*bits) of
        # byte j; stack along a new minor axis then flatten re-interleaves.
        parts = [(packed >> (i * bits)) & mask for i in range(k)]
        codes = jnp.stack(parts, axis=-1).reshape(r, nbytes * k)
    else:
        codes = packed
    out_ref[...] = (codes.astype(jnp.float32) * scale_ref[...]
                    + zero_ref[...]).astype(out_dtype)


def unpack_dequantize_pallas(
    codes: jax.Array,
    scale: jax.Array,
    zero: jax.Array,
    bits: int,
    dtype=jnp.float32,
    *,
    interpret: bool = True,
) -> jax.Array:
    """Fused unpack→dequantize.  codes: (nb, bucket*bits/8) packed u8;
    scale/zero: (nb, 1) f32.  Returns (nb, bucket) values in `dtype`."""
    nb, n_packed = codes.shape
    assert nb % ROWS_PER_TILE == 0, nb
    k = _pack_k(bits)
    bucket = n_packed * k
    grid = (nb // ROWS_PER_TILE,)
    kern = functools.partial(_unpack_dequantize_kernel, bits, dtype)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS_PER_TILE, n_packed), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_TILE, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS_PER_TILE, bucket), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, bucket), dtype),
        interpret=interpret,
    )(codes, scale, zero)


def _dequantize_kernel(out_dtype, codes_ref, scale_ref, zero_ref, out_ref):
    c = _from_u8(codes_ref[...])
    out_ref[...] = (c * scale_ref[...] + zero_ref[...]).astype(out_dtype)


def dequantize_pallas(
    codes: jax.Array,
    scale: jax.Array,
    zero: jax.Array,
    dtype=jnp.float32,
    *,
    interpret: bool = True,
) -> jax.Array:
    """(nb, bucket) u8 codes + (nb, 1) affine -> (nb, bucket) values."""
    nb, bucket = codes.shape
    assert nb % ROWS_PER_TILE == 0, nb
    grid = (nb // ROWS_PER_TILE,)
    kern = functools.partial(_dequantize_kernel, dtype)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS_PER_TILE, bucket), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_TILE, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS_PER_TILE, bucket), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, bucket), dtype),
        interpret=interpret,
    )(codes, scale, zero)
