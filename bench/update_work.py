"""Least bytes of the weight update, counted from the model's shapes.

Every trainable parameter takes part, as the configuration's structure
module counts them (bench/structures/; for the dense decoder the weight
GEMMs and the LM head, the embedding table and the RMSNorm scales). Per
parameter the update must read its gradient once at compute width (2
bytes for a matrix, which the step casts to bf16; 4 for a vector, which
stays f32) and read and write the f32 master weight and AdamW's two
moments once each (24 bytes).
Narrowing the master to the compute copy moves no byte this count
requires, so time spent on it lowers the share read against it.
"""
from __future__ import annotations

import structures

MATRIX_GRAD_BYTES = 2
VECTOR_GRAD_BYTES = 4
STATE_BYTES = 3 * 2 * 4          # master, mu, nu: f32, read and written


def trainable_params(cfg: dict):
    """(parameters in matrices, parameters in vectors)."""
    return structures.of(cfg).trainable_params(cfg)


def weight_update_bytes(cfg: dict) -> int:
    """Least HBM bytes of one update of every trainable parameter."""
    matrices, vectors = trainable_params(cfg)
    return (matrices * (MATRIX_GRAD_BYTES + STATE_BYTES)
            + vectors * (VECTOR_GRAD_BYTES + STATE_BYTES))
