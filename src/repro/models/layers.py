"""Shared model building blocks (per-device code, shard_map-native).

Conventions (see core/tp.py):
  * activations entering TP-sharded compute pass through tp_copy;
  * row-parallel outputs pass through tp_reduce;
  * everything here consumes *gathered, TP-local* weights (the QSDP engine
    materializes them per layer inside the step).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.tp import tp_copy, tp_reduce
from ..kernels.ops import RowQuantWeight, rowquant_matmul_dispatch
from ..obs import scoped

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps)).astype(dt) * w.astype(dt)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(var + eps)
    return y.astype(dt) * w.astype(dt) + b.astype(dt)


# ---------------------------------------------------------------------------
# RoPE (1-D and multimodal M-RoPE)
# ---------------------------------------------------------------------------


def rope_cos_sin(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """positions (...,) -> cos/sin of shape (..., head_dim//2), f32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def mrope_cos_sin(
    positions: jax.Array, head_dim: int, theta: float, sections: tuple[int, ...]
) -> tuple[jax.Array, jax.Array]:
    """Qwen2-VL multimodal RoPE.

    positions: (3, ...) — temporal / height / width position streams.
    The head_dim//2 rotary frequencies are partitioned into `sections`
    (sum(sections) == head_dim//2); section s rotates with positions[s].
    """
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang_parts = []
    start = 0
    for s, sec in enumerate(sections):
        ang_parts.append(positions[s].astype(jnp.float32)[..., None] * freqs[start : start + sec])
        start += sec
    ang = jnp.concatenate(ang_parts, axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., n_heads, head_dim); cos/sin: broadcastable (..., head_dim//2).

    Uses the interleaved-halves convention (rotate_half), matching
    Llama/Qwen-family checkpoints.
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :].astype(x.dtype)
    s = sin[..., None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ---------------------------------------------------------------------------
# Vocab-parallel embedding + fused cross-entropy
# ---------------------------------------------------------------------------


def embed_vocab_parallel(tokens: jax.Array, emb_local: jax.Array) -> jax.Array:
    """tokens (B, S) int32; emb_local (V_local, d) — this rank's vocab shard.

    Out-of-shard ids contribute zero; tp_reduce combines the shards.
    Output (B, S, d), replicated over the model axis.
    """
    v_local = emb_local.shape[0]
    rank = lax.axis_index("model")
    ids = tokens - rank * v_local
    in_range = (ids >= 0) & (ids < v_local)
    ids = jnp.clip(ids, 0, v_local - 1)
    out = jnp.take(emb_local, ids, axis=0)
    out = jnp.where(in_range[..., None], out, 0)
    return tp_reduce(out)


@jax.custom_vjp
def vocab_parallel_xent(h: jax.Array, w_local: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token cross-entropy with vocab-parallel logits.

    h: (T, d) final hidden states (replicated over model)
    w_local: (V_local, d) — this rank's shard of the (tied/untied) LM head
    labels: (T,) int32 global ids; negative labels are masked out.

    Never materializes full-vocab logits on one device; the backward
    recomputes the local logits (remat) and returns exact gradients.
    The result is replicated over the model axis; h's cotangent is the
    full (model-summed) one, as required by the tp_copy convention.
    """
    loss, _, _ = _xent_fwd_math(h, w_local, labels)
    return loss


def _xent_fwd_math(h, w_local, labels):
    v_local = w_local.shape[0]
    rank = lax.axis_index("model")
    logits = (h.astype(jnp.float32)) @ (w_local.astype(jnp.float32)).T  # (T, V_local)
    m_loc = jnp.max(logits, axis=-1)
    m = lax.pmax(m_loc, "model")  # fwd-only (custom_vjp controls AD)
    se_loc = jnp.sum(jnp.exp(logits - m[:, None]), axis=-1)
    se = lax.psum(se_loc, "model")
    lse = jnp.log(se) + m  # (T,)
    ids = labels - rank * v_local
    in_range = (ids >= 0) & (ids < v_local)
    ids_c = jnp.clip(ids, 0, v_local - 1)
    picked = jnp.take_along_axis(logits, ids_c[:, None], axis=1)[:, 0]
    tgt = lax.psum(jnp.where(in_range, picked, 0.0), "model")  # (T,)
    mask = (labels >= 0).astype(jnp.float32)
    n = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum((lse - tgt) * mask) / n
    return loss, (m, se, n), (ids_c, in_range, mask)


def _xent_fwd(h, w_local, labels):
    loss, (m, se, n), _ = _xent_fwd_math(h, w_local, labels)
    return loss, (h, w_local, labels, m, se, n)


def _xent_bwd(res, ct):
    h, w_local, labels, m, se, n = res
    v_local = w_local.shape[0]
    rank = lax.axis_index("model")
    hf = h.astype(jnp.float32)
    wf = w_local.astype(jnp.float32)
    logits = hf @ wf.T  # recompute (remat)
    p = jnp.exp(logits - m[:, None]) / se[:, None]  # local softmax slice
    ids = labels - rank * v_local
    in_range = (ids >= 0) & (ids < v_local)
    ids_c = jnp.clip(ids, 0, v_local - 1)
    onehot = (
        jax.nn.one_hot(ids_c, v_local, dtype=jnp.float32) * in_range[:, None].astype(jnp.float32)
    )
    mask = (labels >= 0).astype(jnp.float32)
    dlogits = (p - onehot) * (mask * ct / n)[:, None]  # (T, V_local)
    # h is replicated over model; its true cotangent sums every rank's path.
    dh = lax.psum(dlogits @ wf, "model").astype(h.dtype)
    dw = (dlogits.T @ hf).astype(w_local.dtype)
    return dh, dw, None


vocab_parallel_xent.defvjp(_xent_fwd, _xent_bwd)


def vocab_parallel_logits(h: jax.Array, w_local: jax.Array) -> jax.Array:
    """(T, d) -> (T, V_local) local logit shard (decode path, no grad)."""
    return (h.astype(jnp.float32)) @ (w_local.astype(jnp.float32)).T


def greedy_sample_vocab_parallel(logits_local: jax.Array, v_local: int) -> jax.Array:
    """Argmax over the full (model-sharded) vocab.  logits_local (T, V_local)
    -> (T,) global token ids."""
    rank = lax.axis_index("model")
    m_loc = jnp.max(logits_local, axis=-1)
    a_loc = jnp.argmax(logits_local, axis=-1) + rank * v_local
    m = lax.pmax(m_loc, "model")
    # break ties by smallest id: psum of candidates at the max
    cand = jnp.where(m_loc >= m, a_loc, jnp.iinfo(jnp.int32).max)
    return lax.pmin(cand, "model")


def sample_vocab_parallel(
    logits_local: jax.Array,  # (T, V_local) f32 local logit shard
    v_local: int,
    temp: jax.Array,  # (T,) f32 per-row temperature; <= 0 -> greedy
    top_k: jax.Array,  # (T,) int32 per-row top-k; <= 0 -> full vocab, 1 -> greedy
    key: jax.Array,  # (T, 2) uint32 per-row PRNG keys
) -> jax.Array:
    """Per-row temperature / top-k sampling over model-sharded vocab logits.

    Rows with ``temp <= 0`` or ``top_k == 1`` take the greedy argmax path
    BIT-EXACTLY (same reduction as :func:`greedy_sample_vocab_parallel`), so
    a greedy request under a sampling engine matches a pure-greedy engine.
    Sampling uses the Gumbel-max trick seeded per row, so a row's token
    depends only on its own (logits, temp, top_k, key) — never on what else
    is in the batch — which is what makes continuous-batching runs
    reproducible and slot-isolated.

    The full-vocab logits are re-assembled with one all-gather over the
    model axis; every rank then draws the SAME per-row Gumbel noise and
    takes the same argmax, so the result is model-replicated like the
    greedy path.
    """
    greedy = greedy_sample_vocab_parallel(logits_local, v_local)
    full = lax.all_gather(logits_local, "model", axis=1, tiled=True)  # (T, V)

    def row(lg, t, k, kk):
        v = lg.shape[0]
        # top-k mask: keep logits >= the k-th largest (dynamic per-row k)
        kth = jnp.take(jnp.sort(lg), v - jnp.clip(k, 1, v))
        keep = (k <= 0) | (lg >= kth)
        z = lg / jnp.maximum(t, 1e-6) + jax.random.gumbel(kk, (v,), jnp.float32)
        z = jnp.where(keep, z, -jnp.inf)
        return jnp.argmax(z).astype(jnp.int32)

    sampled = jax.vmap(row)(full, temp, top_k, key)
    use_greedy = (temp <= 0.0) | (top_k == 1)
    return jnp.where(use_greedy, greedy, sampled)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def qmatmul(x: jax.Array, w) -> jax.Array:
    """x @ w where w is either a dense array or a :class:`RowQuantWeight`
    (a gathered weight still in QSDP wire-code form — consumed by the fused
    dequant-matmul kernel without materializing the dense matrix).
    Handles arbitrary leading batch dims on x."""
    if isinstance(w, RowQuantWeight):
        lead = x.shape[:-1]
        y = rowquant_matmul_dispatch(x.reshape(-1, x.shape[-1]), w)
        return y.reshape(*lead, w.codes.shape[1])
    return x @ w


@scoped("mlp")
def swiglu_mlp(x: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    """Column-parallel gate/up, row-parallel down.  Weights may be dense
    arrays (training) or RowQuantWeights (quantized-weight decode)."""
    xi = tp_copy(x)
    g = qmatmul(xi, w_gate)
    u = qmatmul(xi, w_up)
    return tp_reduce(qmatmul(jax.nn.silu(g) * u, w_down))


def gelu_mlp(x: jax.Array, w_up: jax.Array, b_up, w_down: jax.Array, b_down) -> jax.Array:
    """Classic enc-dec FFN (GELU), column->row parallel; biases optional."""
    xi = tp_copy(x)
    u = xi @ w_up
    if b_up is not None:
        u = u + b_up.astype(u.dtype)
    y = tp_reduce(jax.nn.gelu(u) @ w_down)
    if b_down is not None:
        y = y + b_down.astype(y.dtype)
    return y
