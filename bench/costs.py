"""Operations and bytes that the algorithm needs, from shapes alone.

A kernel's or a step's share of the roofline divides these by measured
time, so they count the work the computation requires whatever
implements it: the live K/V tokens of each slot (not whole pages), one
read of each weight, and matrix products at two operations per
multiply-add.  ``s`` is ``model.sizes``.
"""

from __future__ import annotations


def matmul_params(s: dict) -> int:
    """Weights that every token multiplies: each layer's projections and
    the (tied) unembedding; the embedding lookup is not a product."""
    d, h, kv, hd, f = s["d"], s["heads"], s["kv_heads"], s["head_dim"], s["ff"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return s["layers"] * per_layer + s["vocab"] * d


def paged_attention_flops(s: dict, kv_tokens: int) -> float:
    """One layer's decode attention over ``kv_tokens`` live K/V tokens
    summed over the rows: q.k and p.v, per query head."""
    return 4.0 * s["heads"] * s["head_dim"] * kv_tokens


def paged_attention_bytes(s: dict, kv_tokens: int, rows: int, itemsize: int) -> float:
    """One layer's decode attention: every live K and V token read once,
    each row's query read and output written once."""
    kv = 2.0 * kv_tokens * s["kv_heads"] * s["head_dim"]
    qo = 2.0 * rows * s["heads"] * s["head_dim"]
    return (kv + qo) * itemsize


def decode_flops(s: dict, rows: int, kv_tokens: int) -> float:
    """Model operations of ``rows`` decoded tokens whose attention spans
    ``kv_tokens`` live tokens in all."""
    return 2.0 * matmul_params(s) * rows + s["layers"] * paged_attention_flops(s, kv_tokens)


def train_flops_per_token(s: dict, seq_len: int) -> float:
    """Forward and backward operations per trained token (three times
    the forward), causal attention over half the sequence on average;
    recomputation is not counted."""
    attn = s["layers"] * 4.0 * s["heads"] * s["head_dim"] * (seq_len + 1) / 2
    return 3.0 * (2.0 * matmul_params(s) + attn)
