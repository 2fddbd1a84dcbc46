"""Work a cell needs, counted from the model's shapes alone.

Operations and bytes come from the configuration's GEMM and attention
shapes (its structure module's, bench/structures/) at the cell's
declared arithmetic, never from tiles, kernel
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

import structures

OPERAND_BYTES = {"int8": 1, "bf16": 2}
RESULT_BYTES = 2
STAT_BYTES = 4


def weight_gemms(cfg: dict, tokens: int):
    """(name, M, K, N) of every weight GEMM of one forward pass over
    `tokens` tokens, as the configuration's structure has them."""
    return structures.of(cfg).weight_gemms(cfg, tokens)


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a GEMM (the embedding is a gather)."""
    return sum(k * n for _, _, k, n in weight_gemms(cfg, 1))


def attention_fwd_ops(cfg: dict, batch: int, seq: int) -> int:
    """Attention operations of one forward pass, all layers, as the
    configuration's structure has them."""
    return structures.of(cfg).attention_fwd_ops(cfg, batch, seq)


def train_step_ops(cfg: dict, batch: int, seq: int) -> int:
    """Model operations of one training step (forward and backward):
    three passes of every weight GEMM at the rows it takes (6 per GEMM
    parameter and token where each takes every token), plus causal
    attention."""
    return (sum(6 * M * K * N for _, M, K, N in
                weight_gemms(cfg, batch * seq))
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
    L, H, Hkv, hd = structures.of(cfg).attention_shape(cfg)
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
