"""Work a cell needs, counted from the model's shapes alone.

Operations and bytes come from the configuration's GEMM and attention
shapes at the cell's declared arithmetic, never from tiles, kernel
internals or compiled HLO, so that every implementation of the same work
is read against the same count. Recomputation (remat) is not counted.

Conventions:
  * a dense GEMM [M, K] x [K, N] is 2*M*K*N operations; training runs
    each weight GEMM three times (forward, input gradient, weight
    gradient);
  * causal attention over S positions needs, per head and sequence,
    QK^T and PV over the S(S+1)/2 visible pairs: 2 * 2 * hd * S(S+1)/2
    operations forward, and twice that backward (dP, dS->dQ, dK, dV);
  * bytes: operands at the declared width (int8 mantissas: 1 byte, bf16:
    2 bytes; shared exponents, 1/128 of that, are left out), results
    written in bf16 (2 bytes), attention's softmax statistics in f32.
"""
from __future__ import annotations

OPERAND_BYTES = {"int8": 1, "bf16": 2}
RESULT_BYTES = 2
STAT_BYTES = 4


def weight_gemms(cfg: dict, tokens: int):
    """(name, M, K, N) of every weight GEMM of one forward pass over
    `tokens` tokens: each layer's projections, then the LM head."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    per_layer = [("wq", D, H * hd), ("wk", D, Hkv * hd),
                 ("wv", D, Hkv * hd), ("wo", H * hd, D),
                 ("ffn_g", D, F), ("ffn_i", D, F), ("ffn_o", F, D)]
    out = []
    for layer in range(cfg["num_hidden_layers"]):
        out += [(f"{layer}.{n}", tokens, k, n_) for n, k, n_ in per_layer]
    out.append(("head", tokens, D, cfg["vocab_size"]))
    return out


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a GEMM (the embedding is a gather)."""
    return sum(k * n for _, _, k, n in weight_gemms(cfg, 1))


def attention_fwd_ops(cfg: dict, batch: int, seq: int) -> int:
    """Causal attention operations of one forward pass, all layers."""
    H, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return (cfg["num_hidden_layers"] * batch * H
            * 2 * 2 * hd * seq * (seq + 1) // 2)


def train_step_ops(cfg: dict, batch: int, seq: int) -> int:
    """Model operations of one training step (forward and backward):
    6 per GEMM parameter and token, plus causal attention."""
    return (6 * matmul_params(cfg) * batch * seq
            + 3 * attention_fwd_ops(cfg, batch, seq))


def _least(ops, nbytes, peak_ops, bw):
    return max(ops / peak_ops, nbytes / bw)


def gemm_least_seconds(cfg: dict, batch: int, seq: int, arith: str,
                       peak_ops: float, bw: float) -> float:
    """Least time of every weight GEMM of one training step (forward,
    input gradient, weight gradient), each the larger of its operations
    over peak and its bytes over bandwidth."""
    ob = OPERAND_BYTES[arith]
    total = 0.0
    for _, M, K, N in weight_gemms(cfg, batch * seq):
        ops = 2 * M * K * N
        for a, b, c in ((M * K, K * N, M * N),      # y = x W
                        (M * N, K * N, M * K),      # dx = dy W^T
                        (M * K, M * N, K * N)):     # dW = x^T dy
            total += _least(ops, ob * (a + b) + RESULT_BYTES * c,
                            peak_ops, bw)
    return total


def attention_least_seconds(cfg: dict, batch: int, seq: int, arith: str,
                            peak_ops: float, bw: float) -> float:
    """Least time of causal attention in one training step: the forward
    (reads Q, K, V; writes O and the row statistics) and the backward
    (reads Q, K, V, O, dO and the statistics; writes dQ, dK, dV)."""
    L, H, Hkv, hd = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    ob = OPERAND_BYTES[arith]
    q = batch * H * seq * hd          # elements of Q, O, dO, dQ
    kv = batch * Hkv * seq * hd       # elements of K, V, dK, dV
    stats = batch * H * seq
    fwd_ops = attention_fwd_ops(cfg, batch, seq) // L
    fwd = _least(fwd_ops, ob * (q + 2 * kv) + RESULT_BYTES * q
                 + STAT_BYTES * stats, peak_ops, bw)
    bwd = _least(2 * fwd_ops, ob * (3 * q + 2 * kv) + STAT_BYTES * stats
                 + RESULT_BYTES * (q + 2 * kv), peak_ops, bw)
    return L * (fwd + bwd)
