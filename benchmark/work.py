"""The work an algorithm needs, counted from shapes: the yardstick's half of
every roofline and MFU share. Whatever kernel or program implements the work,
these counts do not change, so a later kernel cannot make them stale.

FLOPs count a multiply-add as 2. Recomputed work is never credited.
"""
from __future__ import annotations


def train_flops_per_token(matmul_params: int, layers: int, hidden: int,
                          seq: int, causal: bool) -> float:
    """6N + 12 L h T (forward QK^T and PV are 4 h T a token and layer, and
    the backward twice that), the attention term halved where the model is
    causal: a causal token attends to half of the sequence on average. N is
    the architecture's ``matmul_params``: the parameters that sit in a
    matrix multiplication for every token."""
    attn = 12.0 * layers * hidden * seq
    return 6.0 * matmul_params + (attn / 2.0 if causal else attn)


def forward_flops(matmul_params: int, layers: int, hidden: int, tokens: int,
                  keys: float) -> float:
    """Forward FLOPs of computing ``tokens`` tokens that attend to ``keys``
    keys between them (a token at 0-based position p attends to p + 1)."""
    return 2.0 * matmul_params * tokens + 4.0 * layers * hidden * keys


def attention_flops(batch, heads, tq, tk, head_dim, causal,
                    backward: bool) -> float:
    """QK^T and PV forward (2 matmuls); the backward needs 5 more (dV, dP,
    dQ, dK and the recomputed QK^T that any flash backward must redo from
    the saved statistics; recomputing P is part of the ALGORITHM here, not of
    a rematerialisation policy). Causal halves it."""
    per = 2.0 * batch * heads * tq * tk * head_dim
    f = per * ((2 + 5) if backward else 2)
    return f / 2.0 if causal else f


def attention_bytes(batch, heads, tq, tk, head_dim, itemsize,
                    backward: bool) -> float:
    """Least HBM traffic: read Q, K, V and write O forward; the backward
    reads Q, K, V, O, dO and writes dQ, dK, dV."""
    q = batch * heads * tq * head_dim * itemsize
    kv = batch * heads * tk * head_dim * itemsize
    if backward:
        return (3 * q + 2 * kv) + (q + 2 * kv)
    return 2 * q + 2 * kv


def paged_decode_bytes(live_tokens: int, layers: int, kv_heads: int,
                       head_dim: int, itemsize: int) -> float:
    """Bytes of the live context one decode step must read: the tokens each
    slot really holds, in every layer, K and V. Not the grid the kernel
    walks."""
    return float(live_tokens) * layers * 2 * kv_heads * head_dim * itemsize


def paged_decode_flops(live_tokens: int, layers: int, heads: int,
                       head_dim: int) -> float:
    return 4.0 * float(live_tokens) * layers * heads * head_dim


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of FLOPs over peak FLOP/s and bytes over
    peak bytes/s."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
