"""Attention: GQA with tensor parallelism, flash (chunked) attention for
train/prefill, and sequence-sharded flash-decode for serving.

TP layout
---------
Query heads are padded to a multiple of the model-axis size (`n_heads_padded`;
e.g. qwen1.5-32b 40->48, yi-34b 56->64) and sharded contiguously; padded heads
are hard-masked to zero so they never contribute (their params receive zero
gradient, preserving the logical architecture exactly — see DESIGN.md §5).
KV heads are TP-sharded when `n_kv % tp == 0` ("tp" mode), otherwise the
KV projections are model-replicated ("replicated" mode) — the Megatron
convention for GQA ratios that do not divide.

Decode
------
The KV cache is sharded over the *model* axis along the sequence dim
(uniform across all GQA ratios).  Each rank computes partial attention of
all (gathered) query heads against its sequence chunk and the partials are
combined with a log-sum-exp psum — flash-decoding.  Sliding windows are
ring-buffered; slot validity is computed arithmetically from the step index
so no position book-keeping tensors are needed.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.tp import tp_copy, tp_reduce
from ..obs import scoped
from .layers import apply_rope

MODEL_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    n_heads: int  # logical query heads
    n_kv: int
    head_dim: int
    tp: int  # model-axis size
    causal: bool = True
    sliding_window: int = 0  # 0 = full
    q_chunk: int = 1024
    kv_chunk: int = 1024
    # feed the score/AV matmuls bf16 operands with f32 accumulation (MXU
    # native) instead of f32 operands — §Perf hillclimb knob; the baseline
    # stays f32 to match the unoptimized reference numerics.
    mxu_bf16: bool = False

    @property
    def n_heads_padded(self) -> int:
        return -(-self.n_heads // self.tp) * self.tp

    @property
    def heads_local(self) -> int:
        return self.n_heads_padded // self.tp

    @property
    def kv_mode(self) -> str:
        # TP the KV projections only when shards stay contiguous head blocks:
        # that requires no query-head padding and an integral per-rank group.
        ok = (
            self.n_kv % self.tp == 0
            and self.n_heads_padded == self.n_heads
            and self.n_heads % self.n_kv == 0
        )
        return "tp" if ok else "replicated"

    @property
    def kv_local(self) -> int:
        return self.n_kv // self.tp if self.kv_mode == "tp" else self.n_kv

    @property
    def group(self) -> int:
        # logical GQA group (query heads per kv head); padded query heads are
        # masked so their (clipped) kv index is irrelevant.
        return max(self.n_heads // self.n_kv, 1)


def _local_head_mask(cfg: AttnConfig) -> jax.Array:
    """(heads_local,) 1.0 for real heads, 0.0 for padding (per rank)."""
    rank = lax.axis_index(MODEL_AXIS)
    gidx = rank * cfg.heads_local + jnp.arange(cfg.heads_local)
    return (gidx < cfg.n_heads).astype(jnp.float32)


def _expand_kv_local(k: jax.Array, cfg: AttnConfig) -> jax.Array:
    """Map per-rank KV heads onto per-rank (local) query heads.

    k: (..., kv_local, hd) -> (..., heads_local, hd)
    """
    if cfg.kv_mode == "tp":
        # contiguous blocks: local q head j -> local kv head j // (group)
        reps = cfg.heads_local // max(cfg.kv_local, 1)
        if reps <= 0:  # more kv shards than q heads per rank cannot happen when mode == tp
            raise AssertionError((cfg.heads_local, cfg.kv_local))
        return jnp.repeat(k, reps, axis=-2)
    # replicated: index kv by global q head
    rank = lax.axis_index(MODEL_AXIS)
    gidx = rank * cfg.heads_local + jnp.arange(cfg.heads_local)
    kv_idx = jnp.clip(gidx // cfg.group, 0, cfg.n_kv - 1)
    return jnp.take(k, kv_idx, axis=-2)


# ---------------------------------------------------------------------------
# Flash (chunked) attention — train & prefill
# ---------------------------------------------------------------------------


def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Skv, H, D)  (already head-aligned with q)
    v: jax.Array,
    q_pos: jax.Array,  # (Sq,) global positions
    kv_pos: jax.Array,  # (Skv,)
    causal: bool,
    window: int = 0,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    mxu_bf16: bool = False,
) -> jax.Array:
    """Memory-bounded attention: outer scan over q chunks (rematerialized),
    inner scan over kv chunks with running (max, sumexp, out)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    cq = min(q_chunk, sq)
    ckv = min(kv_chunk, skv)
    assert sq % cq == 0 and skv % ckv == 0, (sq, cq, skv, ckv)
    scale = 1.0 / math.sqrt(d)
    nq, nk = sq // cq, skv // ckv

    qc = q.reshape(b, nq, cq, h, d).transpose(1, 0, 2, 3, 4)
    qp = q_pos.reshape(nq, cq)
    kc = k.reshape(b, nk, ckv, h, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nk, ckv, h, d).transpose(1, 0, 2, 3, 4)
    kp = kv_pos.reshape(nk, ckv)

    def q_block(carry, qblk):
        qi, qpi = qblk  # (B, Cq, H, D), (Cq,)

        def kv_block(st, kblk):
            m, l, o = st
            ki, vi, kpi = kblk
            if mxu_bf16:
                # MXU-native: bf16 operands, f32 accumulation — halves
                # score/probability operand traffic (perf hillclimb P1-1)
                s = jnp.einsum("bqhd,bkhd->bhqk", qi, ki,
                               preferred_element_type=jnp.float32)
            else:
                s = jnp.einsum("bqhd,bkhd->bhqk", qi.astype(jnp.float32),
                               ki.astype(jnp.float32))
            s = s * scale
            msk = jnp.ones((cq, ckv), bool)
            if causal:
                msk &= qpi[:, None] >= kpi[None, :]
            if window:
                msk &= kpi[None, :] > qpi[:, None] - window
            s = jnp.where(msk[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard fully-masked rows
            m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(msk[None, None], p, 0.0)
            corr = jnp.exp(jnp.where(jnp.isinf(m), 0.0, m) - m_safe) * (~jnp.isinf(m))
            l_new = l * corr + jnp.sum(p, axis=-1)
            if mxu_bf16:
                pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(qi.dtype), vi,
                                preferred_element_type=jnp.float32)
            else:
                pv = jnp.einsum("bhqk,bkhd->bhqd", p, vi.astype(jnp.float32))
            o_new = o * corr[..., None] + pv
            return (m_new, l_new, o_new), None

        m0 = jnp.full((b, h, cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, cq), jnp.float32)
        o0 = jnp.zeros((b, h, cq, d), jnp.float32)
        (m, l, o), _ = lax.scan(jax.checkpoint(kv_block), (m0, l0, o0), (kc, vc, kp))
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return carry, out.transpose(0, 2, 1, 3)  # (B, Cq, H, D)

    _, outs = lax.scan(jax.checkpoint(q_block), (), (qc, qp))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Self-attention block (train / prefill)
# ---------------------------------------------------------------------------


@scoped("attention")
def self_attention(
    x: jax.Array,  # (B, S, d) replicated over model
    w: dict,  # gathered TP-local weights: wq,wk,wv,wo (+ optional bq,bk,bv)
    cfg: AttnConfig,
    cos: jax.Array,
    sin: jax.Array,
    positions: jax.Array,  # (S,) int32
    cache_slice: bool = False,
):
    """Returns (out (B,S,d), (k_full, v_full) if cache_slice else None).

    k_full/v_full: (B, S, n_kv, hd) un-expanded KV (for prefill cache build),
    rope-applied.
    """
    b, s, _ = x.shape
    hd = cfg.head_dim
    xi = tp_copy(x)
    q = (xi @ w["wq"]) if "bq" not in w else (xi @ w["wq"] + w["bq"].astype(x.dtype))
    q = q.reshape(b, s, cfg.heads_local, hd)
    k = (xi @ w["wk"]) if "bk" not in w else (xi @ w["wk"] + w["bk"].astype(x.dtype))
    v = (xi @ w["wv"]) if "bv" not in w else (xi @ w["wv"] + w["bv"].astype(x.dtype))
    k = k.reshape(b, s, cfg.kv_local, hd)
    v = v.reshape(b, s, cfg.kv_local, hd)

    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    ke = _expand_kv_local(k, cfg)
    ve = _expand_kv_local(v, cfg)
    o = flash_attention(
        q, ke, ve, positions, positions, cfg.causal, cfg.sliding_window,
        cfg.q_chunk, cfg.kv_chunk, cfg.mxu_bf16,
    )
    o = o * _local_head_mask(cfg)[None, None, :, None].astype(o.dtype)
    out = tp_reduce(o.reshape(b, s, cfg.heads_local * hd) @ w["wo"])
    if not cache_slice:
        return out, None
    # full-KV view for the prefill cache (gather over model in "tp" mode)
    if cfg.kv_mode == "tp":
        k_full = lax.all_gather(k, MODEL_AXIS, axis=2, tiled=True)
        v_full = lax.all_gather(v, MODEL_AXIS, axis=2, tiled=True)
    else:
        k_full, v_full = k, v
    return out, (k_full, v_full)


def cross_attention(
    x: jax.Array,  # (B, S, d)
    memory: jax.Array,  # (B, S_enc, d) encoder output
    w: dict,  # wq,wk,wv,wo (+biases)
    cfg: AttnConfig,
):
    """Encoder-decoder cross attention (no positional rotation, full mask)."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    xi = tp_copy(x)
    mi = tp_copy(memory)
    q = (xi @ w["wq"]).reshape(b, s, cfg.heads_local, hd)
    k = (mi @ w["wk"]).reshape(b, memory.shape[1], cfg.kv_local, hd)
    v = (mi @ w["wv"]).reshape(b, memory.shape[1], cfg.kv_local, hd)
    ke = _expand_kv_local(k, cfg)
    ve = _expand_kv_local(v, cfg)
    s_pos = jnp.arange(s)
    m_pos = jnp.arange(memory.shape[1])
    o = flash_attention(q, ke, ve, s_pos, m_pos, causal=False, window=0,
                        q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                        mxu_bf16=cfg.mxu_bf16)
    o = o * _local_head_mask(cfg)[None, None, :, None].astype(o.dtype)
    return tp_reduce(o.reshape(b, s, cfg.heads_local * hd) @ w["wo"])


# ---------------------------------------------------------------------------
# Flash-decode over a sequence-sharded KV cache
# ---------------------------------------------------------------------------


@scoped("attention")
def decode_new_kv(x: jax.Array, w: dict, cfg: AttnConfig, cos, sin):
    """Project this token's q (all padded heads, gathered) and full-head
    k1/v1 on every rank.  Returns (q_all (B,Hp,hd), k1, v1 (B,n_kv,hd)).

    cos/sin may be (hd//2,) — one shared position — or (B, hd//2) per-slot
    rotations (continuous batching, where every batch slot sits at its own
    sequence position)."""
    b, _ = x.shape
    hd = cfg.head_dim
    q = (x @ w["wq"]) if "bq" not in w else (x @ w["wq"] + w["bq"].astype(x.dtype))
    q = q.reshape(b, cfg.heads_local, hd)
    k1 = (x @ w["wk"]) if "bk" not in w else (x @ w["wk"] + w["bk"].astype(x.dtype))
    v1 = (x @ w["wv"]) if "bv" not in w else (x @ w["wv"] + w["bv"].astype(x.dtype))
    k1 = k1.reshape(b, cfg.kv_local, hd)
    v1 = v1.reshape(b, cfg.kv_local, hd)
    cb = cos[None] if cos.ndim == 1 else cos[:, None]
    sb = sin[None] if sin.ndim == 1 else sin[:, None]
    q = apply_rope(q[:, None], cb, sb)[:, 0]
    k1 = apply_rope(k1[:, None], cb, sb)[:, 0]
    q_all = lax.all_gather(q, MODEL_AXIS, axis=1, tiled=True)  # (B, Hp, hd)
    if cfg.kv_mode == "tp":
        k1 = lax.all_gather(k1, MODEL_AXIS, axis=1, tiled=True)
        v1 = lax.all_gather(v1, MODEL_AXIS, axis=1, tiled=True)
    return q_all, k1, v1


@scoped("attention")
def chunk_new_kv(x: jax.Array, w: dict, cfg: AttnConfig, cos, sin):
    """Multi-token variant of :func:`decode_new_kv` for chunked prefill.

    x: (B, Lq, d) — one prompt chunk per batch slot.  cos/sin are
    (B, Lq, hd//2) per-slot-per-token rotations (each slot's chunk starts
    at its own offset).  Returns (q_all (B, Lq, Hp, hd),
    k1/v1 (B, Lq, n_kv, hd)) — full (padded) query heads gathered, KV
    un-expanded, exactly the shapes the ring cache stores."""
    b, lq, _ = x.shape
    hd = cfg.head_dim
    q = (x @ w["wq"]) if "bq" not in w else (x @ w["wq"] + w["bq"].astype(x.dtype))
    q = q.reshape(b, lq, cfg.heads_local, hd)
    k1 = (x @ w["wk"]) if "bk" not in w else (x @ w["wk"] + w["bk"].astype(x.dtype))
    v1 = (x @ w["wv"]) if "bv" not in w else (x @ w["wv"] + w["bv"].astype(x.dtype))
    k1 = k1.reshape(b, lq, cfg.kv_local, hd)
    v1 = v1.reshape(b, lq, cfg.kv_local, hd)
    q = apply_rope(q, cos, sin)
    k1 = apply_rope(k1, cos, sin)
    q_all = lax.all_gather(q, MODEL_AXIS, axis=2, tiled=True)  # (B, Lq, Hp, hd)
    if cfg.kv_mode == "tp":
        k1 = lax.all_gather(k1, MODEL_AXIS, axis=2, tiled=True)
        v1 = lax.all_gather(v1, MODEL_AXIS, axis=2, tiled=True)
    return q_all, k1, v1


@scoped("attention")
def chunk_attend(
    q_all: jax.Array,  # (B, Lq, Hp, hd) — all (padded) query heads
    k_cache: jax.Array,  # (B, S_loc, n_kv * hd) — this rank's seq chunk,
    v_cache: jax.Array,  # the chunk's own KV already written
    cfg: AttnConfig,
    q_pos: jax.Array,  # (B, Lq) per-slot-per-token query positions
    window: int,
    block_tables: jax.Array = None,  # (B, n_log) paged mode
    block_size: int = 0,
):
    """Multi-query flash-decode over the seq-sharded ring cache — the
    chunked-prefill analogue of :func:`decode_attend`.  Each query token
    attends every ring slot whose held position is causally visible
    (p_s >= 0 and p_s <= its own position); the per-rank partials combine
    with the same log-sum-exp psum.  Padded chunk tokens (beyond a slot's
    valid chunk length) compute garbage that the caller never reads —
    their KV is never written, so nothing they produce can reach a valid
    token.  With ``block_tables`` the caches are the (R, S_row, ...) paged
    pool and are first gathered into each slot's logical view (see
    :func:`paged_gather_kv`) — the math below then runs unchanged, which
    is what makes tokens independent of physical block placement.
    Returns (B, Lq, Hp, hd) f32 (padded heads zero)."""
    b, lq, hp, hd = q_all.shape
    rank = lax.axis_index(MODEL_AXIS)
    if block_tables is not None:
        bl_loc = block_size // cfg.tp
        k_cache = paged_gather_kv(k_cache, block_tables, bl_loc)
        v_cache = paged_gather_kv(v_cache, block_tables, bl_loc)
        s_glob = paged_s_glob(window, block_size, bl_loc)
    else:
        s_loc = k_cache.shape[1]
        s_glob = rank * s_loc + jnp.arange(s_loc)
    k_cache, v_cache = _split_heads(k_cache, hd), _split_heads(v_cache, hd)
    qr, k_cache, v_cache = _kv_major_q(q_all, k_cache, v_cache, cfg)

    # slot validity per query token: slot s holds p_s = q - ((q - s) mod W)
    p_s = q_pos[..., None] - jnp.mod(q_pos[..., None] - s_glob, window)
    valid = (p_s >= 0) & slot_valid_mask(q_pos)[..., None]  # (B, Lq, S_loc)

    scale = 1.0 / math.sqrt(hd)
    s_ij = jnp.einsum("blkgd,bskd->blkgs", qr, k_cache.astype(qr.dtype),
                      preferred_element_type=jnp.float32) * scale
    s_ij = jnp.where(valid[:, :, None, None, :], s_ij, -jnp.inf)
    m = lax.pmax(jnp.max(s_ij, axis=-1), MODEL_AXIS)  # (B, Lq, K, G)
    m_safe = jnp.where(jnp.isinf(m), 0.0, m)
    p = jnp.exp(s_ij - m_safe[..., None])
    p = jnp.where(valid[:, :, None, None, :], p, 0.0)
    l = lax.psum(jnp.sum(p, axis=-1), MODEL_AXIS)
    o = lax.psum(
        jnp.einsum("blkgs,bskd->blkgd", p.astype(q_all.dtype),
                   v_cache.astype(q_all.dtype),
                   preferred_element_type=jnp.float32),
        MODEL_AXIS)
    o = o / jnp.maximum(l, 1e-30)[..., None]
    o = o.reshape(b, lq, cfg.n_heads, hd)
    if hp > cfg.n_heads:  # padded heads contribute zero
        o = jnp.pad(o, ((0, 0), (0, 0), (0, hp - cfg.n_heads), (0, 0)))
    return o


def ring_slot(pos: jax.Array, window: int, s_loc: int):
    """Ring-buffer addressing: (local slot index, is_mine flag).

    Elementwise, so ``pos`` may be a scalar (whole batch at one position)
    or a (B,) vector of per-slot positions (continuous batching)."""
    rank = lax.axis_index(MODEL_AXIS)
    slot = jnp.mod(pos, window)
    owner = slot // s_loc
    return slot - owner * s_loc, owner == rank


def slot_valid_mask(pos: jax.Array) -> jax.Array:
    """THE dead-lane test: ``pos >= 0``.

    ``pos = -1`` is the sentinel for a lane that must be inert — retired,
    never filled, or mid-chunked-prefill.  Every consumer of the sentinel
    (the KV write mask in ``DecodeModel._write_token_kv``, the attend
    validity in :func:`decode_attend` / :func:`chunk_attend`, and the
    sampling clamp that keeps dead rows on the draw-free greedy path) goes
    through this one helper so a new cache layout — e.g. the paged block
    pool — cannot re-introduce a stale-lane write by re-deriving the test
    locally and getting an edge wrong."""
    return jnp.asarray(pos) >= 0


# ---------------------------------------------------------------------------
# Paged block-pool addressing (vLLM-style; see serve/kv_pool.py)
# ---------------------------------------------------------------------------
#
# The pool cache keeps the ring tensors' exact shape — (R, S_row, n_kv * hd)
# per layer per rank, S_row the per-rank row length — but reinterprets each
# row as `S_row // block_loc` physical blocks of block_loc tokens
# (block_loc = block_size // tp: every block is sequence-sharded across all
# model ranks, so ANY table permutation stays rank-local).  A slot's logical
# ring of `window` positions maps through its block table
# bt[j] -> physical block id, with logical position p living at logical
# block (p % window) // block_size, within-block offset p % block_size,
# owner rank (offset // block_loc).
#
# Because attend first GATHERS the slot's blocks into logical order, the
# attention math downstream is literally the ring math on the gathered view
# — outputs are bit-identical for every physical placement of the table's
# blocks by construction (a gather changes no values).


def paged_gather_kv(cache: jax.Array, block_tables: jax.Array,
                    block_loc: int) -> jax.Array:
    """(R, S_row, ...) pool -> (B, n_log * block_loc, ...) per-slot logical
    view through bt (B, n_log) physical block ids (the trailing axes pass
    through).  Unallocated table entries (< 0) clamp to block 0 — garbage
    the caller's validity mask must exclude (it does: they can only cover
    positions beyond the slot's write head)."""
    r, s_row, *tail = cache.shape
    bpr = s_row // block_loc
    pool = cache.reshape(r * bpr, block_loc, *tail)
    b, n_log = block_tables.shape
    view = pool[jnp.clip(block_tables, 0, r * bpr - 1)]
    return view.reshape(b, n_log * block_loc, *tail)


def paged_s_glob(window: int, block_size: int, block_loc: int) -> jax.Array:
    """Global ring offsets held by this rank's slice of the gathered
    logical view (the paged analogue of ``rank * s_loc + arange(s_loc)``):
    gathered index i sits in logical block i // block_loc at within-block
    offset rank * block_loc + i % block_loc."""
    rank = lax.axis_index(MODEL_AXIS)
    i = jnp.arange((window // block_size) * block_loc)
    return (i // block_loc) * block_size + rank * block_loc + i % block_loc


def paged_slot(pos: jax.Array, window: int, block_size: int, block_loc: int,
               block_tables: jax.Array):
    """Paged write addressing: (pool row, per-rank row seq index, is_mine).

    pos is (B,) or (B, Lq) global positions; block_tables (B, n_log).
    is_mine is False for positions another rank's block slice holds —
    combined with the caller's validity mask and a drop-mode scatter this
    is the paged analogue of :func:`ring_slot`."""
    rank = lax.axis_index(MODEL_AXIS)
    lp = jnp.mod(pos, window)
    j = lp // block_size
    o = lp % block_size
    flat_j = j.reshape(j.shape[0], -1)
    phys = jnp.take_along_axis(block_tables, flat_j, axis=1).reshape(j.shape)
    bpr = window // block_size  # pool rows are ring-length: blocks per row
    row = phys // bpr
    seq = (phys % bpr) * block_loc + o % block_loc
    return row, seq, (o // block_loc) == rank


def _split_heads(cache: jax.Array, hd: int) -> jax.Array:
    """(B, S, n_kv * hd) cache view -> (B, S, n_kv, hd); a view already
    split passes unchanged.  Split after the layer's view is gathered, so
    only that view takes the head-major layout."""
    return cache.reshape(*cache.shape[:2], -1, hd)


def _kv_major_q(q_all: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                cfg: AttnConfig):
    """Arrange the real query heads kv-major for the batched decode einsums.

    q_all is (..., Hp, hd) — the leading dims pass through unchanged
    (decode: (B,); chunked prefill: (B, Lq)).  Regular GQA
    (n_heads == n_kv * group) reshapes q to (..., n_kv, group, hd) and
    attends the un-expanded cache directly (no group-x cache copy —
    §Perf P2-2).  Irregular ratios (e.g. n_kv > n_heads, where the reshape
    is impossible) gather each query head's kv head from the cache instead
    and run the same einsums with a per-head group of 1."""
    *lead, _, hd = q_all.shape
    if cfg.n_heads == cfg.n_kv * cfg.group:
        return (q_all[..., : cfg.n_heads, :].reshape(
                    *lead, cfg.n_kv, cfg.group, hd),
                k_cache, v_cache)
    kv_idx = jnp.clip(jnp.arange(cfg.n_heads) // cfg.group, 0, cfg.n_kv - 1)
    return (q_all[..., : cfg.n_heads, :].reshape(*lead, cfg.n_heads, 1, hd),
            jnp.take(k_cache, kv_idx, axis=2),
            jnp.take(v_cache, kv_idx, axis=2))


@scoped("attention")
def decode_attend(
    q_all: jax.Array,  # (B, Hp, hd) — all (padded) query heads
    k_cache: jax.Array,  # (B, S_loc, n_kv * hd) — this rank's seq chunk,
    v_cache: jax.Array,  # current token's KV already written
    cfg: AttnConfig,
    pos: jax.Array,
    window: int,
    block_tables: jax.Array = None,  # (B, n_log) paged mode
    block_size: int = 0,
):
    """Flash-decode over the seq-sharded ring cache WITHOUT materializing a
    GQA-expanded KV copy: real query heads are reshaped kv-major (see
    :func:`_kv_major_q`, which also handles irregular GQA ratios like
    n_kv > n_heads) and the score/AV einsums batch over the kv-head axis
    directly against the un-expanded cache — this removed a group-x
    cache-sized copy per layer (§Perf P2-2).  bf16 operands, f32
    accumulation.  Returns (B, Hp, hd) f32 (padded heads zero).

    ``pos`` may be a scalar (one shared position) or a (B,) vector of
    per-slot positions (continuous batching) — slot validity is computed
    per batch element either way.  With ``block_tables`` the caches are
    the (R, S_row, ...) paged pool: each slot's blocks are gathered into
    logical ring order first (:func:`paged_gather_kv`), so the math below
    — and therefore every output bit — is independent of the physical
    placement, sharing, or fragmentation of the table's blocks.

    Validity geometry is what makes multi-position speculative steps
    safe with NO extra masking here: the draft rounds and the verify
    pass (``DecodeModel.verify_fn``) leave stale draft-precision KV at
    ring slots AHEAD of a lane's committed position, but slot ``s`` is
    valid for a query at ``pos`` only when ``p_s <= pos`` (the ring-wrap
    residue above is <= pos by construction), so a query can never read
    a position it hasn't passed — and every caller that advances ``pos``
    through a drafted position rewrites that slot's KV in its own
    precision *before* the query reaches it (write-before-attend)."""
    b, hp, hd = q_all.shape
    rank = lax.axis_index(MODEL_AXIS)
    if block_tables is not None:
        bl_loc = block_size // cfg.tp
        k_cache = paged_gather_kv(k_cache, block_tables, bl_loc)
        v_cache = paged_gather_kv(v_cache, block_tables, bl_loc)
        s_glob = paged_s_glob(window, block_size, bl_loc)
    else:
        s_loc = k_cache.shape[1]
        s_glob = rank * s_loc + jnp.arange(s_loc)
    k_cache, v_cache = _split_heads(k_cache, hd), _split_heads(v_cache, hd)
    qr, k_cache, v_cache = _kv_major_q(q_all, k_cache, v_cache, cfg)

    # slot validity: slot s (global) holds position p_s = pos - ((pos-s) mod W)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    p_s = pos[:, None] - jnp.mod(pos[:, None] - s_glob[None, :], window)
    valid = (p_s >= 0) & slot_valid_mask(pos)[:, None]  # (B, S_loc)

    scale = 1.0 / math.sqrt(hd)
    s_ij = jnp.einsum("bkgd,bskd->bkgs", qr, k_cache.astype(qr.dtype),
                      preferred_element_type=jnp.float32) * scale
    s_ij = jnp.where(valid[:, None, None, :], s_ij, -jnp.inf)
    m = lax.pmax(jnp.max(s_ij, axis=-1), MODEL_AXIS)  # (B, K, G)
    m_safe = jnp.where(jnp.isinf(m), 0.0, m)
    p = jnp.exp(s_ij - m_safe[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = lax.psum(jnp.sum(p, axis=-1), MODEL_AXIS)
    o = lax.psum(
        jnp.einsum("bkgs,bskd->bkgd", p.astype(q_all.dtype),
                   v_cache.astype(q_all.dtype),
                   preferred_element_type=jnp.float32),
        MODEL_AXIS)
    o = o / jnp.maximum(l, 1e-30)[..., None]
    o = o.reshape(b, cfg.n_heads, hd)
    if hp > cfg.n_heads:  # padded heads contribute zero
        o = jnp.pad(o, ((0, 0), (0, hp - cfg.n_heads), (0, 0)))
    return o


@scoped("attention")
def decode_out_proj(o: jax.Array, w: dict, cfg: AttnConfig, dtype) -> jax.Array:
    """(B, Hp, hd) f32 attention output -> (B, d) via the TP-local slice of
    the row-parallel wo + psum."""
    b = o.shape[0]
    rank = lax.axis_index(MODEL_AXIS)
    o_loc = lax.dynamic_slice(
        o, (0, rank * cfg.heads_local, 0), (b, cfg.heads_local, cfg.head_dim)
    ).astype(dtype)
    return lax.psum(o_loc.reshape(b, cfg.heads_local * cfg.head_dim) @ w["wo"],
                    MODEL_AXIS)


def decode_self_attention(
    x: jax.Array,  # (B, d) current token hidden
    w: dict,
    cfg: AttnConfig,
    k_cache: jax.Array,  # (B, S_loc, n_kv, hd) — this rank's seq chunk
    v_cache: jax.Array,
    pos: jax.Array,  # scalar int32 — index of the current token
    cos: jax.Array,  # (hd//2,) rope at `pos`
    sin: jax.Array,
    window: int,  # ring size == S_loc * tp
):
    """One-token decode with the cache slices held by the caller.  Returns
    (out (B,d), new_k_cache, new_v_cache) — the caller may instead use
    decode_new_kv/ring_slot/decode_attend to write a scan-carried stacked
    cache in place (models/decode.py does; see §Perf P2)."""
    b, _ = x.shape
    hd = cfg.head_dim
    s_loc = k_cache.shape[1]
    q_all, k1, v1 = decode_new_kv(x, w, cfg, cos, sin)
    idx, is_mine = ring_slot(pos, window, s_loc)
    mine = is_mine.astype(k_cache.dtype)
    old_k = lax.dynamic_slice(k_cache, (0, idx, 0, 0), (b, 1, cfg.n_kv, hd))[:, 0]
    old_v = lax.dynamic_slice(v_cache, (0, idx, 0, 0), (b, 1, cfg.n_kv, hd))[:, 0]
    k_cache = lax.dynamic_update_slice(
        k_cache, (mine * k1 + (1 - mine) * old_k)[:, None].astype(k_cache.dtype),
        (0, idx, 0, 0))
    v_cache = lax.dynamic_update_slice(
        v_cache, (mine * v1 + (1 - mine) * old_v)[:, None].astype(v_cache.dtype),
        (0, idx, 0, 0))
    o = decode_attend(q_all, k_cache, v_cache, cfg, pos, window)
    out = decode_out_proj(o, w, cfg, x.dtype)
    return out, k_cache, v_cache


def decode_cross_attention(
    x: jax.Array,  # (B, d)
    w: dict,
    cfg: AttnConfig,
    ck_cache: jax.Array,  # (B, S_enc_loc, n_kv, hd) precomputed encoder KV
    cv_cache: jax.Array,
    enc_len: jax.Array,  # scalar — valid encoder length
):
    b, _ = x.shape
    hd = cfg.head_dim
    s_loc = ck_cache.shape[1]
    rank = lax.axis_index(MODEL_AXIS)
    q = (x @ w["wq"]).reshape(b, cfg.heads_local, hd)
    q_all = lax.all_gather(q, MODEL_AXIS, axis=1, tiled=True)
    qr, ck_cache, cv_cache = _kv_major_q(q_all, ck_cache, cv_cache, cfg)
    valid = (rank * s_loc + jnp.arange(s_loc)) < enc_len
    scale = 1.0 / math.sqrt(hd)
    s_ij = jnp.einsum("bkgd,bskd->bkgs", qr, ck_cache.astype(qr.dtype),
                      preferred_element_type=jnp.float32) * scale
    s_ij = jnp.where(valid[None, None, None, :], s_ij, -jnp.inf)
    m = lax.pmax(jnp.max(s_ij, axis=-1), MODEL_AXIS)
    m_safe = jnp.where(jnp.isinf(m), 0.0, m)
    p = jnp.where(valid[None, None, None, :], jnp.exp(s_ij - m_safe[..., None]), 0.0)
    l = lax.psum(jnp.sum(p, axis=-1), MODEL_AXIS)
    o = lax.psum(
        jnp.einsum("bkgs,bskd->bkgd", p.astype(q_all.dtype),
                   cv_cache.astype(q_all.dtype),
                   preferred_element_type=jnp.float32), MODEL_AXIS)
    o = o / jnp.maximum(l, 1e-30)[..., None]
    o = o.reshape(b, cfg.n_heads, hd)
    if cfg.n_heads_padded > cfg.n_heads:
        o = jnp.pad(o, ((0, 0), (0, cfg.n_heads_padded - cfg.n_heads), (0, 0)))
    return decode_out_proj(o, w, cfg, x.dtype)
