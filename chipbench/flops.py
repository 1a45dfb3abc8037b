"""Model operations and bytes of the configurations, from their shapes.

Counts are of the model, not of what a program happens to compute: a
matrix product of (m, k) by (k, n) is 2*m*k*n operations; the attention
of a token that sees ``ctx`` positions is 2*2*ctx*heads*head_dim (scores
and values); a mixture-of-experts layer counts its ``top_k`` experts and
the router, not the capacity slots; the head counts the published
vocabulary. A training step is three forward passes (forward, and the
backward's two products per forward product); recomputation is not
counted. A causal sequence of S tokens is counted as S tokens that see
S/2 positions each.
"""
from __future__ import annotations

import math

import numpy as np

from .weights import dims, shapes


def layer_matmul_params(c: dict) -> int:
    """Weights of one layer that a token multiplies by."""
    m = dims(c)
    d, hd = m["d"], m["head_dim"]
    attn = d * m["heads"] * hd * 2 + d * m["kv_heads"] * hd * 2
    if m["experts"]:
        ffn = m["top_k"] * 3 * d * m["ff"] + d * m["experts"]
    else:
        ffn = 3 * d * m["ff"]
    return attn + ffn


def head_params(c: dict) -> int:
    m = dims(c)
    return m["vocab"] * m["d"]


def attention_flops(c: dict, ctx: float) -> float:
    """Scores and values of one token over ``ctx`` positions, all layers."""
    m = dims(c)
    return 4.0 * ctx * m["heads"] * m["head_dim"] * m["layers"]


def forward_flops_per_token(c: dict, ctx: float, head: bool = True) -> float:
    m = dims(c)
    f = 2.0 * layer_matmul_params(c) * m["layers"] + attention_flops(c, ctx)
    return f + (2.0 * head_params(c) if head else 0.0)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    return 3.0 * forward_flops_per_token(c, seq_len / 2)


def prefill_flops(c: dict, batch: int, prompt_len: int) -> float:
    """A prompt batch through every layer, and the head at its last token."""
    per_seq = prompt_len * forward_flops_per_token(c, prompt_len / 2, head=False) \
        + 2.0 * head_params(c)
    return batch * per_seq


def decode_flops(c: dict, batch: int, length: int) -> float:
    """One decode step of ``batch`` tokens at position ``length``: each
    sees ``length + 1`` positions."""
    return batch * forward_flops_per_token(c, length + 1)


def weight_bytes(c: dict) -> int:
    """Bytes of all weights as they are stored (embedding read once, tied)."""
    return sum(math.prod(s) * np.dtype(dt).itemsize for s, dt, _ in shapes(c).values())


def decode_bytes(c: dict, batch: int, cache_len: int, cache_itemsize: int = 2) -> int:
    """Bytes a decode step must read: every weight once, and the K and V
    caches of every layer at their allocated length."""
    m = dims(c)
    cache = 2 * m["layers"] * batch * cache_len * m["kv_heads"] * m["head_dim"]
    return weight_bytes(c) + cache * cache_itemsize
