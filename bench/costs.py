"""Operations and bytes that the work needs, counted from a configuration's
shapes (never from what a kernel happens to launch), so that a change that
fuses or replaces a kernel is judged against the same work.
"""
from __future__ import annotations


def dense_matrices(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, elements, gathers per training step) of every weight matrix of
    the dense GQA transformer: the embedding, tied to the LM head, gathered
    once per step, and each layer's projections, gathered in the forward
    and again in the rematerialized backward."""
    d, L = cfg["d_model"], cfg["n_layers"]
    q = cfg["n_heads"] * cfg["head_dim"]
    kv = cfg["n_kv_heads"] * cfg["head_dim"]
    ff = cfg["d_ff"]
    out = [("embed", cfg["vocab_size"] * d, 1)]
    for name, n in (("wq", d * q), ("wk", d * kv), ("wv", d * kv), ("wo", q * d),
                    ("w_gate", d * ff), ("w_up", d * ff), ("w_down", ff * d)):
        out += [(f"layers/{name}", n, 2)] * L
    return out


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul per token: every layer's
    projections and the (tied) LM head; the embedding lookup is not one."""
    return sum(n for _, n, _ in dense_matrices(cfg))


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOP per trained token, forward and backward, recomputation not
    counted: 6 per matmul parameter, plus causal attention's scores and
    weighted sum (2 x 2 x seq/2 x heads x head_dim per layer forward, times
    3 with the backward)."""
    attn = 6 * cfg["n_layers"] * seq_len * cfg["n_heads"] * cfg["head_dim"]
    return 6.0 * matmul_params(cfg) + attn


def quant_bytes_per_step(cfg: dict, qsdp: dict, chips: int) -> float:
    """HBM bytes one device's quantize and dequantize work needs in one QSDP
    training step.

    Per weight gather: read the f32 shard and write its codes and per-bucket
    (scale, zero); read every shard's codes and metadata and write the bf16
    tensor. Per gradient reduce-scatter: read the f32 gradient and write its
    codes and metadata; read the codes and metadata received and write the
    f32 shard.  Tensors under ``min_quant_size`` travel unquantized."""
    wb, gb = qsdp["weight_bits"] / 8.0, qsdp["grad_bits"] / 8.0
    meta = 8.0 / qsdp["bucket_size"]  # f32 scale + zero per bucket, per element
    total = 0.0
    for _, n, gathers in dense_matrices(cfg):
        if n < qsdp["min_quant_size"]:
            continue
        local = -(-n // chips)
        gather = (4 * local + (wb + meta) * local
                  + (wb + meta) * n + 2 * n)
        reduce = (4 * n + (gb + meta) * n
                  + (gb + meta) * n + 4 * local)
        total += gathers * gather + reduce
    return total
