"""Mixture-of-experts feed-forward block with the program's capacity rule.

Routing: softmax over the router's logits, the top ``k`` experts of each
token, their weights renormalised to sum to one.

Capacity (as the program dispatches, one sequence at a time): the ``S * k``
choices of a sequence are taken in token order (token ``t``'s ``j``-th
choice is choice ``t * k + j``). Each expert keeps its first ``cap`` choices
in that order, ``cap = ceil(ceil(S * k * capacity_factor) / E)``, and the
rest are dropped: a dropped choice adds nothing to its token's output.
HF's granitemoe drops nothing; this rule is the program's, stated in the
configuration file.

Load-balancing loss, added to the loss once per layer:
``E * sum_e mean_t(gate_te) * mean_t(top1_t == e) * aux_weight``.

Each expert's block is computed for every token and weighted by the
token's kept weight for it (zero where not chosen): plain, at E/k times
the work of a dispatch.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .numerics import HIGHEST, mm


def capacity(seq: int, config: dict) -> int:
    e, k = int(config["num_local_experts"]), int(config["num_experts_per_tok"])
    cf = float(config["assumed"]["capacity_factor"])
    return int(math.ceil(int(math.ceil(seq * k * cf)) / e))


def ffn(p: dict, x, config: dict, lowp: bool):
    """x (S, D) float32 -> (out (S, D), aux loss)."""
    s = x.shape[0]
    e, k = int(config["num_local_experts"]), int(config["num_experts_per_tok"])
    logits = jnp.matmul(x, p["router"].astype(jnp.float32), precision=HIGHEST)
    gates = jax.nn.softmax(logits, axis=-1)                     # (S, E)
    top_w, top_i = jax.lax.top_k(gates, k)                      # (S, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    chosen = jax.nn.one_hot(top_i.reshape(s * k), e, dtype=jnp.int32)
    rank = jnp.cumsum(chosen, axis=0) - chosen                  # earlier choices
    keep = jnp.sum(rank * chosen, axis=-1) < capacity(s, config)
    w = (top_w.reshape(s * k) * keep)[:, None] * chosen         # (S*k, E)
    weight = w.reshape(s, k, e).sum(axis=1)                     # (S, E)

    def expert(out, ep):
        pe, we = ep
        h = jax.nn.silu(mm(x, pe["w_gate"], lowp)) * mm(x, pe["w_up"], lowp)
        return out + we[:, None] * mm(h, pe["w_down"], lowp), None

    experts = {n: p[n] for n in ("w_gate", "w_up", "w_down")}
    out, _ = jax.lax.scan(jax.checkpoint(expert),
                          jnp.zeros_like(x), (experts, weight.T))
    aux_w = float(config["assumed"]["moe_aux_loss_weight"])
    top1 = jax.nn.one_hot(top_i[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.sum(jnp.mean(gates, 0) * jnp.mean(top1, 0)) * aux_w
    return out, aux
