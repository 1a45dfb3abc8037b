"""Mixture-of-Experts FFN: a grouped, dropless dispatch where no choice can
be dropped, and a capacity dispatch with HeMT skewed-capacity routing.

Which path a layer takes follows from shapes alone. Each sequence is a
group of ``S`` tokens, and a token picks an expert at most once, so when
every expert's capacity for the group is at least ``S`` no choice can be
dropped. A call of one such sequence takes the grouped path: it sorts the
``S*k`` choices by expert and runs each expert weight as one grouped
product over its rows (``jax.lax.ragged_dot``), the published layer with no
padding. Every other call takes the capacity path, which keeps a fixed
number of slots per expert and drops the choices past them; it drops
nothing where every capacity holds the sequence. A call of several
sequences stays on it because it sorts each sequence on its own: the
grouped path's one sort over all of a call's rows would cross the batch,
and under data parallelism GSPMD would gather the rows of every shard.

The paper's Algorithm 1 (skewed hash partitioner) buckets shuffle records by
capacity-weighted ranges. In the MoE "shuffle" (token -> expert-shard
dispatch) the capacity path applies the same idea: per-expert slot
capacities are made proportional to the expert *shard* capacity vector
supplied by the HeMT planner, so a slow or contended expert shard receives
proportionally fewer tokens before overflow-drop, shrinking the
synchronization delay at the MoE barrier (the all-to-all + combine).

The capacity dispatch is sort-based and *grouped by batch row*: each
sequence dispatches its own tokens, so under batch-sharded data parallelism
the sort stays local to the shard (no global resort — the collective cost is
only the buffer all-to-all that expert parallelism itself requires).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple

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


class MoEStats(NamedTuple):
    """Rows of an expert dispatch, int32 scalars that add up over layers and
    grains: the routed choices, those dropped past an expert's capacity, and
    the largest expert group of each sequence's ``S*k`` choices."""
    routed_rows: jnp.ndarray
    dropped_rows: jnp.ndarray
    max_expert_rows: jnp.ndarray

    @staticmethod
    def zeros() -> "MoEStats":
        return MoEStats(*(jnp.zeros((), jnp.int32) for _ in range(3)))

    def plus(self, other: "MoEStats") -> "MoEStats":
        return MoEStats(*(a + b for a, b in zip(self, other)))


def is_dropless(cfg: MoEConfig, tokens_per_group: int) -> bool:
    """True when every expert's capacity for a group of ``tokens_per_group``
    tokens holds them all, so that no choice can be dropped."""
    return int(expert_capacities(cfg, tokens_per_group).min()) >= tokens_per_group


def takes_grouped_path(cfg: MoEConfig, batch: int, tokens_per_group: int) -> bool:
    """True when a call of ``batch`` sequences of ``tokens_per_group``
    tokens takes the grouped path: one sequence, which no expert's capacity
    can cut short."""
    return batch == 1 and is_dropless(cfg, tokens_per_group)


def moe_apply(params: Params, x: jnp.ndarray, cfg: MoEConfig, act: str = "silu",
              constrain=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D). Returns (out (B,S,D), aux_loss scalar): ``moe_layer``
    for callers that do not read its row counts."""
    out, aux, _ = moe_layer(params, x, cfg, act, constrain=constrain)
    return out, aux


def moe_layer(params: Params, x: jnp.ndarray, cfg: MoEConfig, act: str = "silu",
              constrain=None) -> Tuple[jnp.ndarray, jnp.ndarray, MoEStats]:
    """x: (B, S, D). Returns (out (B,S,D), aux_loss scalar, MoEStats).

    constrain: optional sharding hook; the capacity path's dispatch buffers
    get kind "moe_buffer" = (batch over data, experts over "model", slots,
    d) — the expert-parallel all-to-all layout. Without it GSPMD is free to
    leave the (B, E*cap, D) scatter buffer replicated over the model axis."""
    e, k = cfg.n_experts, cfg.top_k
    logits = router_logits(x, params["router"])                 # (B, S, E)
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(gates, k)                       # (B, S, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # ---- load-balancing aux loss (switch-style) --------------------------
    me = jnp.mean(gates, axis=(0, 1))                            # (E,)
    ce = jnp.mean(jax.nn.one_hot(top_i[..., 0], e), axis=(0, 1))
    aux = e * jnp.sum(me * ce) * cfg.aux_loss_weight

    activation = jax.nn.silu if act == "silu" else jax.nn.gelu
    if takes_grouped_path(cfg, x.shape[0], x.shape[1]):
        out, stats = _grouped(params, x, top_w, top_i, e, activation)
    else:
        out, stats = _capacity(params, x, top_w, top_i, cfg, activation, constrain)
    return out, aux, stats


def router_logits(x: jnp.ndarray, router: jnp.ndarray) -> jnp.ndarray:
    """(..., E) logits of the float32 router, computed in float32. At its
    default precision a TPU runs a float32 product as one bf16 pass, which
    rounds the router's weights before the top-k choice."""
    return jnp.matmul(x.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def _permute_rows(x: jnp.ndarray, perm: jnp.ndarray, inv: jnp.ndarray) -> jnp.ndarray:
    """x[perm] for a permutation ``perm`` with inverse ``inv``; its gradient
    is a gather by ``inv``, not the scatter-add a gather's transpose is."""
    return x[perm]


def _permute_rows_fwd(x, perm, inv):
    return x[perm], (perm, inv)


def _permute_rows_bwd(res, g):
    perm, inv = res
    return g[inv], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _grouped(params, x, top_w, top_i, e, activation):
    """Dropless dispatch of one sequence: its S*k choices sorted by expert,
    one grouped product per expert weight over the sorted rows, then
    unsorted and summed per token with its gate weights in float32."""
    b, s, d = x.shape
    k = top_i.shape[-1]
    n = b * s * k
    with jax.named_scope("moe_dispatch"):
        expert = top_i.reshape(n)
        order = jnp.argsort(expert, stable=True)                     # sorted row -> choice
        inv = jnp.zeros((n,), order.dtype).at[order].set(
            jnp.arange(n, dtype=order.dtype))                        # choice -> sorted row
        sizes = jnp.bincount(expert, length=e).astype(jnp.int32)     # rows per expert
        # choice t*k + j is token t's j-th expert: repeat each token k times
        rows = jnp.broadcast_to(x.reshape(b * s, 1, d), (b * s, k, d)).reshape(n, d)
        rows = _permute_rows(rows, order, inv)                       # (n, D) by expert

    with jax.named_scope("moe_experts"):
        up = jax.lax.ragged_dot(rows, params["w_up"], sizes)
        if "w_gate" in params:
            up = activation(jax.lax.ragged_dot(rows, params["w_gate"], sizes)) * up
        else:
            up = activation(up)
        y = jax.lax.ragged_dot(up, params["w_down"], sizes)          # (n, D)

    with jax.named_scope("moe_combine"):
        y = _permute_rows(y, inv, order).reshape(b, s, k, d)
        out = jnp.sum(y.astype(jnp.float32) * top_w[..., None], axis=2)
    stats = MoEStats(jnp.asarray(n, jnp.int32), jnp.zeros((), jnp.int32),
                     jnp.max(sizes))
    return out.astype(x.dtype), stats


def _capacity(params, x, top_w, top_i, cfg: MoEConfig, activation, constrain):
    """Per-sequence slots of fixed capacity per expert; choices past an
    expert's capacity, in token order, are dropped."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    caps_np = expert_capacities(cfg, s)
    cap_buf = int(caps_np.max())  # rectangular buffer: max per-expert capacity
    caps = jnp.asarray(caps_np)

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
            lambda row: jnp.searchsorted(row, jnp.arange(e + 1), side="left"))(exp_s)
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
    stats = MoEStats(jnp.asarray(b * s * k, jnp.int32),
                     jnp.sum(~keep).astype(jnp.int32),
                     jnp.sum(jnp.max(jnp.diff(starts, axis=1), axis=1)).astype(jnp.int32))
    return out, stats


def moe_apply_dense_fallback(params: Params, x: jnp.ndarray, cfg: MoEConfig,
                             act: str = "silu") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle: route every token through its top-k experts exactly (no
    capacity drop). O(T * E) compute — used by tests as reference."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = router_logits(x, params["router"])
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
