"""Mixture-of-Experts FFN with capacity-based sort dispatch and HeMT
skewed-capacity routing.

The paper's Algorithm 1 (skewed hash partitioner) buckets shuffle records by
capacity-weighted ranges. In the MoE "shuffle" (token -> expert-shard
dispatch) we apply the same idea: per-expert slot capacities are made
proportional to the expert *shard* capacity vector supplied by the HeMT
planner, so a slow or contended expert shard receives proportionally fewer
tokens before overflow-drop, shrinking the synchronization delay at the MoE
barrier (the all-to-all + combine).

Dispatch is sort-based and *grouped by batch row*: each sequence dispatches
its own tokens, so under batch-sharded data parallelism the sort stays local
to the shard (no global resort — the collective cost is only the buffer
all-to-all that expert parallelism itself requires).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models.layers import Params, _dense_init


def moe_init(key, d_model: int, d_ff: int, cfg: MoEConfig, glu: bool,
             dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 4)
    e = cfg.n_experts
    scale = 1.0 / math.sqrt(d_model)
    p = {
        "router": _dense_init(ks[0], d_model, e, dtype=jnp.float32),
        "w_up": (jax.random.normal(ks[1], (e, d_model, d_ff), jnp.float32)
                 * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[2], (e, d_ff, d_model), jnp.float32)
                   * (1.0 / math.sqrt(d_ff))).astype(dtype),
    }
    if glu:
        p["w_gate"] = (jax.random.normal(ks[3], (e, d_model, d_ff), jnp.float32)
                       * scale).astype(dtype)
    return p


def expert_capacities(cfg: MoEConfig, tokens_per_group: int):
    """Per-expert slot capacities (E,) — static numpy int array.

    Homogeneous: C_e = ceil(T*k/E * capacity_factor) for all e.
    HeMT (shard_capacities set): C_e proportional to relative shard capacity
    (paper Sec. 5.1: d_i = D * v_i / V), rounded by largest remainder so that
    sum stays equal to the homogeneous total (fixed buffer footprint).
    """
    import numpy as np
    e, k = cfg.n_experts, cfg.top_k
    total = int(math.ceil(tokens_per_group * k * cfg.capacity_factor))
    if cfg.shard_capacities is None:
        per = int(math.ceil(total / e))
        return np.full((e,), per, np.int32)
    v = np.asarray(cfg.shard_capacities, np.float64)
    share = v / v.sum() * total
    base = np.floor(share).astype(np.int32)
    rem = int(total - base.sum())
    order = np.argsort(-(share - np.floor(share)))
    base[order[:rem]] += 1
    return base


def moe_apply(params: Params, x: jnp.ndarray, cfg: MoEConfig, act: str = "silu",
              constrain=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D). Returns (out (B,S,D), aux_loss scalar).

    constrain: optional sharding hook; the dispatch buffers get kind
    "moe_buffer" = (batch over data, experts over "model", slots, d) — the
    expert-parallel all-to-all layout. Without it GSPMD is free to leave
    the (B, E*cap, D) scatter buffer replicated over the model axis."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    caps_np = expert_capacities(cfg, s)
    cap_buf = int(caps_np.max())  # rectangular buffer: max per-expert capacity
    caps = jnp.asarray(caps_np)

    logits = (x.astype(jnp.float32) @ params["router"])          # (B, S, E)
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(gates, k)                       # (B, S, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # ---- load-balancing aux loss (switch-style) --------------------------
    me = jnp.mean(gates, axis=(0, 1))                            # (E,)
    ce = jnp.mean(jax.nn.one_hot(top_i[..., 0], e), axis=(0, 1))
    aux = e * jnp.sum(me * ce) * cfg.aux_loss_weight

    # ---- sort-based grouped dispatch -------------------------------------
    with jax.named_scope("moe_dispatch"):
        # flatten expert choices per batch row: (B, S*k)
        exp_flat = top_i.reshape(b, s * k)
        w_flat = top_w.reshape(b, s * k)
        tok_flat = jnp.broadcast_to(jnp.arange(s)[:, None], (s, k)).reshape(s * k)
        tok_flat = jnp.broadcast_to(tok_flat, (b, s * k))

        order = jnp.argsort(exp_flat, axis=-1, stable=True)          # (B, S*k)
        exp_s = jnp.take_along_axis(exp_flat, order, -1)
        tok_s = jnp.take_along_axis(tok_flat, order, -1)
        w_s = jnp.take_along_axis(w_flat, order, -1)

        # position within its expert run: exp_s is sorted, so the run start of
        # expert e is searchsorted(exp_s, e) — O(S*k*logE) and (B, E) memory
        # instead of the (B, S*k, E) cumsum tensor (16.8 GB/layer for dbrx)
        starts = jax.vmap(
            lambda row: jnp.searchsorted(row, jnp.arange(e), side="left"))(exp_s)
        pos_in_exp = jnp.arange(s * k)[None, :] - jnp.take_along_axis(
            starts, exp_s, axis=1)                                   # (B, S*k)

        keep = pos_in_exp < caps[exp_s]
        slot = jnp.where(keep, exp_s * cap_buf + jnp.minimum(pos_in_exp, cap_buf - 1),
                         e * cap_buf)                                # drop slot

        # scatter tokens into (B, E*cap+1, D) then drop the overflow row
        src = jnp.take_along_axis(x, tok_s[..., None], axis=1)       # (B, S*k, D)
        buf = jnp.zeros((b, e * cap_buf + 1, d), x.dtype)
        buf = jax.vmap(lambda bf, sl, sr: bf.at[sl].set(sr))(buf, slot, src)
        buf = buf[:, : e * cap_buf].reshape(b, e, cap_buf, d)
        if constrain is not None:
            buf = constrain(buf, kind="moe_buffer")   # the EP all-to-all

    # ---- expert FFN -------------------------------------------------------
    with jax.named_scope("moe_experts"):
        activation = jax.nn.silu if act == "silu" else jax.nn.gelu
        up = jnp.einsum("becd,edf->becf", buf, params["w_up"])
        if "w_gate" in params:
            gate = jnp.einsum("becd,edf->becf", buf, params["w_gate"])
            up = activation(gate) * up
        else:
            up = activation(up)
        out_buf = jnp.einsum("becf,efd->becd", up, params["w_down"])
        if constrain is not None:
            out_buf = constrain(out_buf, kind="moe_buffer")
        out_buf = out_buf.reshape(b, e * cap_buf, d)
        out_buf = jnp.concatenate([out_buf, jnp.zeros((b, 1, d), x.dtype)], axis=1)

    # ---- combine -----------------------------------------------------------
    with jax.named_scope("moe_combine"):
        gathered = jax.vmap(lambda bf, sl: bf[sl])(out_buf, slot)    # (B, S*k, D)
        gathered = gathered * (w_s * keep)[..., None].astype(x.dtype)
        out = jnp.zeros((b, s, d), x.dtype)
        out = jax.vmap(lambda o, t, g: o.at[t].add(g))(out, tok_s, gathered)
    return out, aux


def moe_apply_dense_fallback(params: Params, x: jnp.ndarray, cfg: MoEConfig,
                             act: str = "silu") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle: route every token through its top-k experts exactly (no
    capacity drop). O(T * E) compute — used by tests as reference."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = x.astype(jnp.float32) @ params["router"]
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(gates, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    weights = jax.vmap(jax.vmap(lambda i, v: jnp.zeros((e,), jnp.float32)
                                .at[i].set(v)))(top_i, top_w)

    activation = jax.nn.silu if act == "silu" else jax.nn.gelu
    up = jnp.einsum("bsd,edf->besf", x, params["w_up"])
    if "w_gate" in params:
        gate = jnp.einsum("bsd,edf->besf", x, params["w_gate"])
        up = activation(gate) * up
    else:
        up = activation(up)
    per_exp = jnp.einsum("besf,efd->besd", up, params["w_down"])
    out = jnp.einsum("besd,bse->bsd", per_exp.astype(jnp.float32), weights)

    me = jnp.mean(gates, axis=(0, 1))
    ce = jnp.mean(jax.nn.one_hot(top_i[..., 0], e), axis=(0, 1))
    aux = e * jnp.sum(me * ce) * cfg.aux_loss_weight
    return out.astype(x.dtype), aux
