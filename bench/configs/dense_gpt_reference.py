"""Plain float32 reference of the dense GQA transformer that the gpt-350m
and gpt-1.3b configurations describe, written from the equations and
sharing no code or state with the program under test.

Block (pre-norm): ``x += Attn(RMSNorm(x))``, ``x += SwiGLU(RMSNorm(x))``,
with rotary position embedding on queries and keys (rotate-half), causal
softmax attention, grouped KV heads, and the token embedding tied to the
LM head.  Initial weights follow the configuration's stated rule from the
seed: the names sorted, the i-th drawn from ``fold_in(key, i)``; the
embedding ``N(0, 0.02^2)``, the projections ``N(0, 1/fan_in)``, the output
projections (``wo``, ``w_down``) further scaled by ``1/sqrt(2 * n_layers)``,
the norms 1.  AdamW with a linear warm-up then cosine decay, and the
gradient clipped by its global norm, as the configuration states.

Every matmul runs at ``highest`` precision.  Training gradients are summed
over blocks of rows and serving logits are computed one sequence at a
time, so that the reference fits one chip beside nothing else.

``bits`` turns on the control: weights pass through bucketed min-max
quantization with one random shift per bucket, and gradients through
bucketed stochastic rounding, at that width.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

LAYER_KEYS = ("attn_norm", "mlp_norm", "w_down", "w_gate", "w_up", "wk", "wo",
              "wq", "wv")


def shapes(cfg: dict) -> dict:
    """(shape, init) of every parameter, by the program's leaf names."""
    d, L, ff = cfg["d_model"], cfg["n_layers"], cfg["d_ff"]
    q, kv = cfg["n_heads"] * cfg["head_dim"], cfg["n_kv_heads"] * cfg["head_dim"]
    out_scale = 1.0 / math.sqrt(2 * L)
    return {
        "embed": ((cfg["vocab_size"], d), ("normal", 0.02)),
        "final_norm": ((d,), ("ones", 0)),
        "layers/attn_norm": ((L, d), ("ones", 0)),
        "layers/mlp_norm": ((L, d), ("ones", 0)),
        "layers/wq": ((L, d, q), ("fan_in", 1.0)),
        "layers/wk": ((L, d, kv), ("fan_in", 1.0)),
        "layers/wv": ((L, d, kv), ("fan_in", 1.0)),
        "layers/wo": ((L, q, d), ("fan_in", out_scale)),
        "layers/w_gate": ((L, d, ff), ("fan_in", 1.0)),
        "layers/w_up": ((L, d, ff), ("fan_in", 1.0)),
        "layers/w_down": ((L, ff, d), ("fan_in", out_scale)),
    }


def init_params(cfg: dict, key: jax.Array) -> dict:
    out = {}
    for i, (name, (shape, (kind, scale))) in enumerate(sorted(shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if kind == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        elif kind == "normal":
            out[name] = jax.random.normal(k, shape, jnp.float32) * scale
        else:
            out[name] = jax.random.normal(k, shape, jnp.float32) * (
                scale / math.sqrt(shape[-2]))
    return out


# -- the control's quantizers ---------------------------------------------------

def _buckets(x, bucket):
    flat = x.reshape(-1)
    pad = (-flat.size) % bucket
    return jnp.pad(flat, (0, pad)).reshape(-1, bucket), pad


def fake_quant(x, bits: int, bucket: int, key, mode: str):
    """Bucketed min-max quantize then dequantize: ``shift`` rounds to nearest
    after one uniform shift per bucket, ``stochastic`` rounds each value up
    with the probability of its fraction."""
    levels = 2 ** bits - 1
    b, pad = _buckets(x, bucket)
    lo = b.min(axis=1, keepdims=True)
    scale = jnp.maximum((b.max(axis=1, keepdims=True) - lo) / levels, 1e-12)
    v = (b - lo) / scale
    if mode == "shift":
        r = jax.random.uniform(key, (b.shape[0], 1), minval=-0.5, maxval=0.5)
        c = jnp.clip(jnp.round(v - r), 0, levels) + r
    else:
        f = jnp.floor(v)
        c = f + (jax.random.uniform(key, v.shape) < v - f)
    y = (c * scale + lo).reshape(-1)
    return y[: y.size - pad].reshape(x.shape)


def _quant_layerwise(p: dict, bits, bucket, key, mode):
    """Quantize each matrix one layer slice at a time (as it is gathered)."""
    out = {}
    for i, (name, w) in enumerate(sorted(p.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("norm"):  # norms travel unquantized
            out[name] = w
        elif w.ndim == 3:
            ks = jax.random.split(k, w.shape[0])
            out[name] = jax.vmap(lambda a, kk: fake_quant(a, bits, bucket, kk, mode))(w, ks)
        else:
            out[name] = fake_quant(w, bits, bucket, k, mode)
    return out


# -- the model ----------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x (..., S, H, hd): rotate-half rotary embedding by position."""
    s, hd = x.shape[-3], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def block(cfg, x, w):
    """One layer on x (B, S, d)."""
    b, s, _ = x.shape
    H, KV, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    h = rms_norm(x, w["attn_norm"], cfg["norm_eps"])
    q = rope((h @ w["wq"]).reshape(b, s, H, hd), cfg["rope_theta"])
    k = rope((h @ w["wk"]).reshape(b, s, KV, hd), cfg["rope_theta"])
    v = (h @ w["wv"]).reshape(b, s, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, H * hd)
    x = x + o @ w["wo"]
    h = rms_norm(x, w["mlp_norm"], cfg["norm_eps"])
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def hidden(cfg, params, tokens, quant=None):
    """Final normed hidden states (B, S, d) of token rows (B, S).  With
    `quant` = (bits, bucket, key), each layer's matrices pass through the
    control's weight quantizer as the layer runs (no second copy of the
    weights is held)."""
    x = params["embed"][tokens]
    layers = {k: params[f"layers/{k}"] for k in LAYER_KEYS}

    def body(c, inp):
        i, w = inp
        if quant is not None:
            bits, bucket, key = quant
            w = _quant_layerwise(w, bits, bucket, jax.random.fold_in(key, i), "shift")
        return block(cfg, c, w), None

    x, _ = lax.scan(jax.checkpoint(body), x, (jnp.arange(cfg["n_layers"]), layers))
    return rms_norm(x, params["final_norm"], cfg["norm_eps"])


def mean_loss(cfg, params, tokens, labels):
    h = hidden(cfg, params, tokens)
    logits = h @ params["embed"].T
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


# -- training -----------------------------------------------------------------

def lr_at(train: dict, step):
    """Linear warm-up to ``lr``, then cosine decay to a tenth of it."""
    lr, warm, total = train["lr"], train["warmup_steps"], train["total_steps"]
    step = jnp.asarray(step, jnp.float32)
    t = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = lr * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(math.pi * t)))
    return jnp.where(step < warm, lr * step / max(warm, 1), cos)


@partial(jax.jit, static_argnums=(0, 4, 5, 6))
def _grads(cfg_items, params, tokens, labels, rows, bits, bucket, key):
    """Mean loss and gradient over all rows, summed over blocks of `rows`;
    with `bits`, weights and gradients pass through the control's
    quantizers."""
    cfg = dict(cfg_items)
    n = tokens.shape[0]
    blocks = (tokens.reshape(n // rows, rows, -1), labels.reshape(n // rows, rows, -1))
    if bits:
        wq = _quant_layerwise(params, bits, bucket, jax.random.fold_in(key, 1), "shift")
    else:
        wq = params

    def body(acc, blk):
        loss, g = jax.value_and_grad(lambda p: mean_loss(cfg, p, *blk))(wq)
        return jax.tree.map(jnp.add, acc, (loss, g)), None

    zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
    (loss, g), _ = lax.scan(body, zero, blocks)
    loss, g = loss / (n // rows), jax.tree.map(lambda a: a / (n // rows), g)
    if bits:
        g = _quant_layerwise(g, bits, bucket, jax.random.fold_in(key, 2), "stochastic")
    return loss, g


@partial(jax.jit, static_argnums=(0,))
def _adamw(train_items, params, g, mu, nu, step):
    t = dict(train_items)
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, t["grad_clip"] / jnp.maximum(gn, 1e-12))
    g = jax.tree.map(lambda x: x * scale, g)
    mu = jax.tree.map(lambda m, x: t["b1"] * m + (1 - t["b1"]) * x, mu, g)
    nu = jax.tree.map(lambda v, x: t["b2"] * v + (1 - t["b2"]) * x * x, nu, g)
    c1, c2 = 1 - t["b1"] ** step, 1 - t["b2"] ** step
    lr = lr_at(t, step)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + t["eps"])
                                  + t["weight_decay"] * p), params, mu, nu)
    return params, mu, nu, g


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(x * x)) for k, x in tree.items()}


def leaf_norms(tree) -> dict:
    return {k: float(v) for k, v in _norms(tree).items()}


def train_steps(cfg: dict, train: dict, key, batches, rows: int, bits: int = 0,
                bucket: int = 1024, rows_used=None) -> dict:
    """Run ``len(batches)`` AdamW steps from the seeded initial weights.

    Returns each step's loss, the per-leaf norms of the first raw gradient
    and of the first clipped gradient (what the optimizer gets), and the
    per-leaf norms of the parameters' change over all the steps.
    `rows_used` keeps only that many leading rows of each batch (a fault)."""
    cfg_items = tuple(sorted(cfg.items()))
    train_items = tuple(sorted(train.items()))
    with jax.default_matmul_precision("highest"):
        params = init_params(cfg, key)
        p0 = params
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        out = {"loss": []}
        for i, (tokens, labels) in enumerate(batches):
            if rows_used is not None:
                tokens, labels = tokens[:rows_used], labels[:rows_used]
            loss, g = _grads(cfg_items, params, jnp.asarray(tokens), jnp.asarray(labels),
                             min(rows, tokens.shape[0]), bits, bucket,
                             jax.random.fold_in(key, 1000 + i))
            out["loss"].append(float(loss))
            if i == 0:
                out["grad_raw"] = leaf_norms(g)
            params, mu, nu, gc = _adamw(train_items, params, g, mu, nu, i + 1)
            if i == 0:
                out["grad"] = leaf_norms(gc)
            del g, gc
        out["update"] = leaf_norms(jax.tree.map(jnp.subtract, params, p0))
    return out


# -- serving ------------------------------------------------------------------

@partial(jax.jit, static_argnums=(0, 5, 6))
def _gaps(cfg_items, params, seq, n_prompt, n_served, bits, bucket, key):
    """For one sequence (prompt, then served tokens, then padding): the
    widest gap by which a served token's logit lies below the best one, or
    with `bits` (the control), the widest gap of the token that the weights
    quantized to `bits` put first at each served position."""
    cfg = dict(cfg_items)
    logits = hidden(cfg, params, seq[None])[0] @ params["embed"].T  # (T, V)
    pos = jnp.arange(seq.shape[0])
    served = (pos >= n_prompt - 1) & (pos < n_prompt - 1 + n_served)
    if not bits:
        pick = jnp.roll(seq, -1)
    else:
        emb = fake_quant(params["embed"], bits, bucket, jax.random.fold_in(key, 1), "shift")
        hq = hidden(cfg, dict(params, embed=emb), seq[None], (bits, bucket, key))[0]
        pick = jnp.argmax(hq @ emb.T, axis=-1)
    gap = logits.max(axis=-1) - jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0]
    return jnp.max(jnp.where(served, gap, -jnp.inf))


def served_gaps(cfg: dict, key, requests, length: int, bits: int = 0,
                bucket: int = 1024) -> list:
    """Widest gap per (prompt, served tokens) request, each padded to
    `length` so that one program serves every request."""
    cfg_items = tuple(sorted(cfg.items()))
    out = []
    with jax.default_matmul_precision("highest"):
        params = init_params(cfg, key)
        for prompt, served in requests:
            seq = list(prompt) + list(served)
            seq = jnp.asarray(seq + [0] * (length - len(seq)), jnp.int32)
            out.append(float(_gaps(cfg_items, params, seq, len(prompt), len(served),
                                   bits, bucket, jax.random.fold_in(key, 7000))))
    return out
