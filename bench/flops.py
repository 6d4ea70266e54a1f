"""Model FLOPs, computed from a configuration's shapes.

Training counts the forward pass and a backward pass of twice its cost
(3x forward). Recomputation under remat is not counted. A matrix
multiplication of (m, k) by (k, n) is 2mkn. Causal attention is counted
at half: a token at position i attends to about i keys, so its score and
weighted-value products average S/2 keys over a sequence of S. The
embedding lookup is free; the tied read-out over the vocabulary is a
(D, V) matrix multiplication on every token.
"""
from __future__ import annotations

from bench import weights


def lm_forward_flops_per_token(model: dict, seq_len: int) -> float:
    d = weights.dims(model)
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    projections = D * H * hd + 2 * D * KV * hd + H * hd * D
    mlp = 3 * D * F                              # gate, up, down
    attention = 2 * H * hd * (seq_len / 2)       # QK^T and PV, causal half
    per_layer = 2 * (projections + mlp) + 2 * attention
    return d["L"] * per_layer + 2 * D * d["V"]


def lm_train_flops_per_token(model: dict, seq_len: int) -> float:
    return 3.0 * lm_forward_flops_per_token(model, seq_len)
