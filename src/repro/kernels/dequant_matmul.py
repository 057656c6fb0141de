"""Fused int8-dequant matmul — the beyond-paper optimization the QSDP
conclusion points at ("whether the lower-precision weight representation can
also be exploited for faster runtimes").

After a quantized all-gather, the full layer weight exists on-device as u8
codes + per-row affine (scale, zero).  The baseline path dequantizes to a
full bf16/f32 matrix in HBM and then matmuls — paying the full-precision
weight bytes from HBM into VMEM *twice* (write then read).  This kernel
consumes the codes directly:

    y[m, n] = sum_k x[m, k] * (c[k, n] * s[k] + z[k])
            = (x * s^T) @ c     +     (x @ z) * 1^T
              ^^^^^^^^^^^^^          ^^^^^^^^^
              MXU int8->f32 dot      rank-1 correction (VPU)

so the weight traffic from HBM is 1 byte/element instead of 2-4, moving the
memory-roofline term down by ~2x for weight-dominated decode steps.

Tiling: grid (M/BM, N/BN, K/BK); x tile (BM, BK) and code tile (BK, BN) live
in VMEM; the accumulator is revisited across the K grid dimension (output
BlockSpec ignores k), with MXU-aligned tile sizes (multiples of 128 on the
minor dims, 8 on sublanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dqmm_kernel(seg_tiles: int, x_ref, c_ref, s_ref, z_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # The affine arrives transposed, (n_seg, BK): segments on sublanes, K on
    # lanes, so the selected row broadcasts straight across x's K lanes.  The
    # row is picked with a one-hot sum (exact: one nonzero term).
    seg = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 0)
    pick = seg == pl.program_id(1) // seg_tiles
    s = jnp.sum(jnp.where(pick, s_ref[...], 0.0), axis=0, keepdims=True)  # (1, BK)
    z = jnp.sum(jnp.where(pick, z_ref[...], 0.0), axis=0, keepdims=True)  # (1, BK)
    x = x_ref[...].astype(jnp.float32)  # (BM, BK)
    # u8 -> f32 through int32: Mosaic has no direct u8 -> f32 cast
    c = c_ref[...].astype(jnp.int32).astype(jnp.float32)  # (BK, BN)
    acc = jnp.dot(x * s, c, preferred_element_type=jnp.float32)  # scale folded into x
    acc += jnp.sum(x * z, axis=1, keepdims=True)  # rank-1 term
    o_ref[...] += acc


def rowquant_matmul_pallas(
    x: jax.Array,
    codes: jax.Array,
    scale: jax.Array,
    zero: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = True,
) -> jax.Array:
    """y = x @ dequant(W).

    x: (M, K) f32/bf16; codes: (K, N) u8; scale, zero: (K, n_seg) f32 with
    the affine constant over N-segments of size N / n_seg (n_seg == 1 is
    per-row affine).  Each n-tile must lie inside one segment (block_n
    divides the segment — arranged upstream in ops.py).  The affine is
    handed to the kernel as (n_seg, BK) blocks, whose last two dims obey
    the TPU's (8, 128) block rule (full n_seg rows, BK lanes), and the
    kernel selects its tile's segment row.
    Shapes must tile evenly (pad upstream in ops.py).
    """
    m, k = x.shape
    k2, n = codes.shape
    assert k == k2, (k, k2)
    n_seg = scale.shape[1]
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    seg_tiles = (n // bn) // n_seg  # n-tiles per affine segment
    assert seg_tiles * n_seg == n // bn, (n, bn, n_seg)
    grid = (m // bm, n // bn, k // bk)
    kern = functools.partial(_dqmm_kernel, seg_tiles)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((n_seg, bk), lambda i, j, kk: (0, kk)),
            pl.BlockSpec((n_seg, bk), lambda i, j, kk: (0, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, codes, scale.T, zero.T)
    return out.astype(x.dtype)
