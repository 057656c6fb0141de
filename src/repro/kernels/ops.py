"""Jit'd public wrappers around the Pallas kernels + backend dispatch.

Dispatch knobs
--------------
Two environment variables (plus per-call overrides) control how the QSDP
hot path runs:

  * ``REPRO_QUANT_BACKEND`` — ``"pallas" | "jnp" | "auto"`` (default
    ``auto``).  ``auto`` selects the Pallas kernels on TPU and whenever
    ``REPRO_PALLAS_INTERPRET`` is set truthy (interpret-mode testing on
    CPU), otherwise the pure-jnp reference in ``core.quant``.  The two
    backends are bit-exact (tested), so this is purely a performance knob.
  * ``REPRO_PALLAS_INTERPRET`` — force (``1``) or forbid (``0``) Pallas
    interpret mode.  Unset: interpret off-TPU, compiled on TPU.  Forcing
    interpret mode on a TPU is an error: it would run the kernels through
    the interpreter on the host and hide the compiled path.

``core.quant.quantize`` / ``dequantize`` call :func:`quantize_packed` /
:func:`dequantize_packed` here when the resolved backend is ``pallas``; the
wire layout (packed u8 codes + per-bucket f32 scale/zero) is identical in
both backends — see the module docstring of ``kernels.quantize`` for the
exact byte layout.
"""
from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import ref
from .dequant_matmul import rowquant_matmul_pallas
from .quantize import (
    ROWS_PER_TILE,
    dequantize_pallas,
    quantize_pack_pallas,
    quantize_pallas,
    unpack_dequantize_pallas,
)


def _interpret_env() -> bool | None:
    """REPRO_PALLAS_INTERPRET as a tri-state: None when unset, else its
    truthiness ("0"/"false"/"False" are the falsy spellings)."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is None:
        return None
    return env not in ("0", "false", "False")


def _default_interpret() -> bool:
    env = _interpret_env()
    on_tpu = jax.default_backend() == "tpu"
    if env and on_tpu:
        raise RuntimeError("REPRO_PALLAS_INTERPRET is set truthy on a TPU; "
                           "unset it to run the compiled kernels")
    return not on_tpu if env is None else env


def resolve_backend(backend: str | None = None) -> str:
    """Resolve a ``"pallas" | "jnp" | "auto" | None`` request to a concrete
    backend.  An explicit "pallas"/"jnp" wins; None or "auto" defers to
    ``REPRO_QUANT_BACKEND``, and a still-"auto" answer picks Pallas on TPU
    or when ``REPRO_PALLAS_INTERPRET`` forces interpret mode on, and the
    jnp reference otherwise."""
    b = backend or "auto"
    if b == "auto":
        b = os.environ.get("REPRO_QUANT_BACKEND", "auto")
    assert b in ("pallas", "jnp", "auto"), b
    if b != "auto":
        return b
    if jax.default_backend() == "tpu" or _interpret_env():
        return "pallas"
    return "jnp"


def _pad_rows(x: jax.Array, mult: int) -> tuple[jax.Array, int]:
    nb = x.shape[0]
    pad = (-nb) % mult
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, nb


@partial(jax.jit, static_argnames=("levels", "stochastic", "interpret"))
def quantize_buckets(
    x: jax.Array,
    rand: jax.Array,
    levels: int = 255,
    stochastic: bool = True,
    interpret: bool | None = None,
):
    """Bucket-quantize a (nb, bucket) f32 array.  Returns (codes, scale, zero)
    with scale/zero shaped (nb, 1)."""
    interpret = _default_interpret() if interpret is None else interpret
    xp, nb = _pad_rows(x, ROWS_PER_TILE)
    rp, _ = _pad_rows(rand, ROWS_PER_TILE)
    codes, scale, zero = quantize_pallas(xp, rp, levels, stochastic, interpret=interpret)
    return codes[:nb], scale[:nb], zero[:nb]


@partial(jax.jit, static_argnames=("interpret",))
def dequantize_buckets(
    codes: jax.Array, scale: jax.Array, zero: jax.Array, interpret: bool | None = None
):
    interpret = _default_interpret() if interpret is None else interpret
    cp, nb = _pad_rows(codes, ROWS_PER_TILE)
    sp, _ = _pad_rows(scale, ROWS_PER_TILE)
    zp, _ = _pad_rows(zero, ROWS_PER_TILE)
    out = dequantize_pallas(cp, sp, zp, interpret=interpret)
    return out[:nb]


# ---------------------------------------------------------------------------
# Fused quantize->pack / unpack->dequantize (the core.quant hot path)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("levels", "bits", "mode", "rand_scale", "interpret"))
def quantize_packed(
    x: jax.Array,
    rand: jax.Array,
    levels: int,
    bits: int,
    mode: str = "nearest",
    rand_scale: float = 1.0,
    interpret: bool | None = None,
):
    """Fused bucketed quantize + bit-pack of a (nb, bucket) f32 array.

    Returns (packed codes u8 (nb, bucket*bits/8 — or one byte per code when
    8 % bits != 0), scale (nb, 1), zero (nb, 1)); the exact wire layout of
    ``core.quant.Quantized``.  `rand` is mode-dependent (see
    ``kernels.quantize.quantize_pack_pallas``)."""
    interpret = _default_interpret() if interpret is None else interpret
    xp, nb = _pad_rows(x, ROWS_PER_TILE)
    rp, _ = _pad_rows(rand, ROWS_PER_TILE)
    codes, scale, zero = quantize_pack_pallas(
        xp, rp, levels, bits, mode, rand_scale, interpret=interpret
    )
    return codes[:nb], scale[:nb], zero[:nb]


@partial(jax.jit, static_argnames=("bits", "dtype", "interpret"))
def dequantize_packed(
    codes: jax.Array,
    scale: jax.Array,
    zero: jax.Array,
    bits: int,
    dtype=jnp.float32,
    interpret: bool | None = None,
):
    """Fused bit-unpack + affine dequantize: (nb, bucket*bits/8) packed u8
    codes + (nb, 1) scale/zero -> (nb, bucket) values in `dtype`."""
    interpret = _default_interpret() if interpret is None else interpret
    cp, nb = _pad_rows(codes, ROWS_PER_TILE)
    sp, _ = _pad_rows(scale, ROWS_PER_TILE)
    zp, _ = _pad_rows(zero, ROWS_PER_TILE)
    out = unpack_dequantize_pallas(cp, sp, zp, bits, dtype, interpret=interpret)
    return out[:nb]


# ---------------------------------------------------------------------------
# Fused dequant-matmul (serve/decode path)
# ---------------------------------------------------------------------------


class RowQuantWeight(NamedTuple):
    """A (K, N) matmul weight kept in quantized code form.

    codes: (K, N) u8; scale/zero: (K, n_seg) f32 — the affine is per
    (K-row, N-segment) block with segment size N / n_seg.  n_seg == 1 is
    plain per-row quantization (the ``quantize_weight_rowwise`` layout);
    n_seg == N / bucket_size is the QSDP *wire* layout of a row-major
    weight whose rows are a multiple of the bucket size, which lets the
    serve path feed gathered wire codes straight into the matmul without
    ever materializing the dequantized weight (see QSDPEngine.gather_rowquant).
    """

    codes: jax.Array
    scale: jax.Array
    zero: jax.Array


@partial(jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret"))
def rowquant_matmul(
    x: jax.Array,
    codes: jax.Array,
    scale: jax.Array,
    zero: jax.Array,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool | None = None,
):
    """y = x @ dequant(W) consuming u8 codes directly (see dequant_matmul.py).

    scale/zero: (K, 1) per-row affine, or (K, n_seg) segment affine with
    N % n_seg == 0 (block_n is clamped to divide the segment).  Pads M/K (and
    N for the per-row case) up to tile multiples, so arbitrary shapes are
    accepted.
    """
    interpret = _default_interpret() if interpret is None else interpret
    m, k = x.shape
    _, n = codes.shape
    n_seg = scale.shape[1]
    bm, bk = min(block_m, m), min(block_k, k)
    if n_seg == 1:
        bn = min(block_n, n)
    else:
        assert n % n_seg == 0, (n, n_seg)
        seg = n // n_seg
        bn = min(block_n, seg)
        while seg % bn:  # shrink to a divisor of the segment
            bn -= 1
        assert n % bn == 0
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    assert n_seg == 1 or pn == 0, (n, bn, n_seg)
    xp = jnp.pad(x, ((0, pm), (0, pk)))
    cp = jnp.pad(codes, ((0, pk), (0, pn)))
    sp = jnp.pad(scale, ((0, pk), (0, 0)))
    zp = jnp.pad(zero, ((0, pk), (0, 0)))
    out = rowquant_matmul_pallas(
        xp, cp, sp, zp, block_m=bm, block_n=bn, block_k=bk, interpret=interpret
    )
    return out[:m, :n]


def rowquant_matmul_dispatch(x: jax.Array, w: RowQuantWeight,
                             backend: str | None = None) -> jax.Array:
    """Backend-dispatched y = x @ dequant(w) for 2D x."""
    if resolve_backend(backend) == "pallas":
        return rowquant_matmul(x, w.codes, w.scale, w.zero)
    return ref.rowquant_matmul_ref(x, w.codes, w.scale, w.zero)


def quantize_weight_rowwise(w: jax.Array, bits: int = 8):
    """Host/one-time: per-K-row quantization producing the kernel layout."""
    return ref.quantize_rowwise_ref(w, (1 << bits) - 1)
