"""Composable decoder/encoder stacks with scan-over-layer-groups.

Layers are grouped by the config's structural period (gemma3: 6 = 5 local +
1 global; jamba: 8 = 1 attn + 7 mamba with MoE every 2nd layer); parameters
are stacked with a leading ``(n_groups, ...)`` axis and the stack is applied
with ``jax.lax.scan`` so HLO size and compile time stay bounded for 40-72
layer models. Remat (activation checkpointing) wraps the scan body.

Every init function has a mirror ``*_axes`` function returning the same
pytree structure with *logical axis name tuples* instead of arrays; the
runtime maps logical names -> mesh axes (see runtime/sharding.py).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    Params, mlp_apply, mlp_init, rmsnorm, rmsnorm_init,
)

Pytree = Any


# ==========================================================================
# single-layer init / axes / apply
# ==========================================================================

def _layer_init(key, cfg: ModelConfig, idx: int, *, cross: bool = False,
                causal: bool = True, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 4)
    kind = cfg.layer_kind(idx)
    p: Params = {"norm1": rmsnorm_init(cfg.d_model)}
    if kind == "attn":
        p["mixer"] = attn.attention_init(ks[0], cfg.d_model, cfg.attention, dtype)
    else:
        p["mixer"] = ssm_mod.ssm_init(ks[0], cfg.d_model, cfg.ssm, dtype)
    if cross:
        p["norm_cross"] = rmsnorm_init(cfg.d_model)
        p["cross"] = attn.attention_init(ks[1], cfg.d_model, cfg.attention, dtype)
    if cfg.d_ff > 0 and not (kind == "ssm" and cfg.family == "ssm"):
        p["norm2"] = rmsnorm_init(cfg.d_model)
        if cfg.layer_is_moe(idx):
            p["ffn"] = moe_mod.moe_init(ks[2], cfg.d_model, cfg.d_ff, cfg.moe,
                                        cfg.glu, dtype)
        else:
            p["ffn"] = mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg.glu, dtype)
    return p


def _layer_axes(cfg: ModelConfig, idx: int, *, cross: bool = False) -> Pytree:
    """Logical axis names per leaf, mirroring _layer_init structure."""
    kind = cfg.layer_kind(idx)
    ax: Dict[str, Any] = {"norm1": {"scale": (None,)}}
    if kind == "attn":
        ax["mixer"] = {"wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
                       "wv": ("embed", "kv_heads"), "wo": ("heads", "embed")}
    else:
        ax["mixer"] = {"w_in": ("embed", "ssm_inner"),
                       "conv_w": (None, "ssm_conv"), "conv_b": ("ssm_conv",),
                       "a_log": (None,), "dt_bias": (None,), "d_skip": (None,),
                       "gate_norm": {"scale": (None,)},
                       "w_out": ("ssm_inner", "embed")}
    if cross:
        ax["norm_cross"] = {"scale": (None,)}
        ax["cross"] = {"wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
                       "wv": ("embed", "kv_heads"), "wo": ("heads", "embed")}
    if cfg.d_ff > 0 and not (kind == "ssm" and cfg.family == "ssm"):
        ax["norm2"] = {"scale": (None,)}
        if cfg.layer_is_moe(idx):
            ax["ffn"] = {"router": ("embed", None),
                         "w_up": ("expert", "embed", "mlp"),
                         "w_down": ("expert", "mlp", "embed")}
            if cfg.glu:
                ax["ffn"]["w_gate"] = ("expert", "embed", "mlp")
        else:
            ax["ffn"] = {"w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}
            if cfg.glu:
                ax["ffn"]["w_gate"] = ("embed", "mlp")
    return ax


def layer_window(cfg: ModelConfig, j: int) -> int:
    """Lookback of attention sub-layer ``j``, 0 for none: the global layers
    of a local/global pattern see everything, every other layer sees the
    config's sliding window."""
    acfg = cfg.attention
    if acfg.local_global != (0, 0) and cfg.layer_is_global_attn(j):
        return 0
    return acfg.sliding_window


def layer_apply(p: Params, h: jnp.ndarray, cfg: ModelConfig, j: int,
                mixer: Callable[[Params, jnp.ndarray], Tuple[jnp.ndarray, Any]],
                *, enc_out: Optional[jnp.ndarray] = None, constrain=None,
                ) -> Tuple[jnp.ndarray, jnp.ndarray, moe_mod.MoEStats, Any]:
    """Pre-norm residual sub-layer ``j`` of a group, for training, prefill
    and decode alike: norm1 -> mixer -> residual -> cross-attention (where
    the layer has it) -> norm2 -> dense or MoE FFN -> residual.

    ``mixer(params, x) -> (out, extra)`` is the stack's attention or SSM
    call; ``extra`` (a cache, or None) is handed back untouched.
    Returns (h, moe aux loss, moe stats, extra)."""
    aux = jnp.zeros((), jnp.float32)
    stats = moe_mod.MoEStats.zeros()
    out, extra = mixer(p["mixer"], rmsnorm(p["norm1"], h, cfg.norm_eps))
    h = h + out
    if "cross" in p:
        x = rmsnorm(p["norm_cross"], h, cfg.norm_eps)
        h = h + attn.cross_attention(p["cross"], x, enc_out, cfg.attention)
    if "ffn" in p:
        x = rmsnorm(p["norm2"], h, cfg.norm_eps)
        if cfg.layer_is_moe(j):
            out, aux, stats = moe_mod.moe_layer(p["ffn"], x, cfg.moe, cfg.act,
                                                constrain=constrain)
        else:
            out = mlp_apply(p["ffn"], x, cfg.act)
        h = h + out
    return h, aux, stats, extra


_REMAT_POLICIES = {
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


def _remat(group_body: Callable, remat: str) -> Callable:
    """``group_body`` checkpointed under the ``remat`` policy ("full" or
    "dots"; any other name, "none" among them, leaves it as it is)."""
    policy = _REMAT_POLICIES.get(remat)
    return group_body if policy is None else jax.checkpoint(group_body,
                                                            policy=policy)


# ==========================================================================
# stacked (scan) decoder stack
# ==========================================================================

def stack_init(key, cfg: ModelConfig, *, cross: bool = False,
               dtype=jnp.bfloat16) -> Params:
    """Stacked params: each leaf gains a leading (n_groups,) axis."""
    period = cfg.layer_period
    n_groups = cfg.n_layers // period
    assert cfg.n_layers % period == 0, (cfg.n_layers, period)

    def one_group(gkey):
        ks = jax.random.split(gkey, period)
        return {f"sub{j}": _layer_init(ks[j], cfg, j, cross=cross, dtype=dtype)
                for j in range(period)}

    return jax.vmap(one_group)(jax.random.split(key, n_groups))


def stack_axes(cfg: ModelConfig, *, cross: bool = False) -> Pytree:
    period = cfg.layer_period
    group = {f"sub{j}": _layer_axes(cfg, j, cross=cross) for j in range(period)}
    # prepend the scanned "layers" axis (never sharded) to every leaf
    return jax.tree.map(lambda t: ("layers",) + tuple(t), group,
                        is_leaf=lambda t: isinstance(t, tuple))


def stack_apply(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                positions: jnp.ndarray, *, enc_out: Optional[jnp.ndarray] = None,
                causal: bool = True, remat: str = "none", constrain=None,
                ) -> Tuple[jnp.ndarray, jnp.ndarray, moe_mod.MoEStats]:
    """Returns (x, moe aux loss, moe stats summed over layers).

    constrain: optional h -> h sharding hook applied to the residual
    stream at group boundaries (sequence-parallel saved activations)."""
    period = cfg.layer_period
    _count_moe_layers(cfg, x)

    def mixer(j):
        if cfg.layer_kind(j) == "ssm":
            return lambda p, x: (ssm_mod.ssm_apply(p, x, cfg.d_model, cfg.ssm,
                                                   constrain=constrain), None)
        acfg = cfg.attention
        if not causal:
            acfg = attn.AttentionConfig(**{**acfg.__dict__, "causal": False})
        return lambda p, x: (attn.attention_apply(
            p, x, acfg, positions, window=layer_window(cfg, j)), None)

    def group_body(carry, gparams):
        h, aux, stats = carry
        for j in range(period):
            h, aux_j, stats_j, _ = layer_apply(gparams[f"sub{j}"], h, cfg, j,
                                               mixer(j), enc_out=enc_out,
                                               constrain=constrain)
            aux, stats = aux + aux_j, stats.plus(stats_j)
        if constrain is not None:
            h = constrain(h)
        return (h, aux, stats), None

    (x, aux, stats), _ = jax.lax.scan(
        _remat(group_body, remat),
        (x, jnp.zeros((), jnp.float32), moe_mod.MoEStats.zeros()), params)
    return x, aux, stats


def _count_moe_layers(cfg: ModelConfig, x: jnp.ndarray) -> None:
    """Counts, when a stack is traced, its MoE layers by the dispatch path
    that a call on ``x`` (B, S, D) takes."""
    from repro.runtime import telemetry

    n = (cfg.n_layers // cfg.layer_period) * sum(
        cfg.layer_is_moe(j) for j in range(cfg.layer_period))
    if n:
        grouped = moe_mod.takes_grouped_path(cfg.moe, x.shape[0], x.shape[1])
        telemetry.count(f"moe.layers_{'grouped' if grouped else 'capacity'}", n)


# ==========================================================================
# decode caches (stacked to match scan)
# ==========================================================================

def _cache_len(cfg: ModelConfig, j: int, max_len: int) -> int:
    """Slots of attention sub-layer ``j``'s cache: sliding-window layers
    keep only ``window`` (a ring buffer)."""
    window = layer_window(cfg, j)
    return min(max_len, window) if window > 0 else max_len


def stack_init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                     dtype=jnp.bfloat16, has_cross: bool = False) -> Pytree:
    """Per-layer decode caches, stacked (n_groups, ...) like the params.

    Sliding-window layers allocate only ``window`` slots (ring buffer).
    """
    period = cfg.layer_period
    n_groups = cfg.n_layers // period

    def one_layer(j):
        if cfg.layer_kind(j) == "ssm":
            return ssm_mod.init_ssm_cache(batch, cfg.d_model, cfg.ssm, dtype)
        return attn.init_kv_cache(batch, _cache_len(cfg, j, max_len),
                                  cfg.attention, dtype)

    group = {f"sub{j}": one_layer(j) for j in range(period)}
    return jax.tree.map(
        lambda leaf: jnp.broadcast_to(leaf[None], (n_groups,) + leaf.shape), group)


def cache_axes(cfg: ModelConfig) -> Pytree:
    """Logical axes for cache leaves: batch is data-sharded; kv heads on model."""
    period = cfg.layer_period

    def one_layer(j):
        if cfg.layer_kind(j) == "ssm":
            return {"conv": ("layers", "batch", None, "ssm_conv"),
                    "state": ("layers", "batch", "ssm_heads_cache", None, None)}
        return {"k": ("layers", "batch", "kv_heads_cache", "cache_seq", None),
                "v": ("layers", "batch", "kv_heads_cache", "cache_seq", None)}

    return {f"sub{j}": one_layer(j) for j in range(period)}


def stack_prefill(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                  positions: jnp.ndarray, max_len: int, *,
                  enc_out: Optional[jnp.ndarray] = None, remat: str = "none",
                  ) -> Tuple[jnp.ndarray, Pytree, jnp.ndarray]:
    """Full-sequence pass that also builds the decode cache.

    Returns (hidden (B,S,D), cache pytree matching stack_init_cache(max_len),
    moe aux loss). Cache slots follow the decode ring-buffer layout so
    stack_decode_step continues seamlessly with cache_len = S.
    """
    period = cfg.layer_period
    _count_moe_layers(cfg, x)

    def mixer(j):
        if cfg.layer_kind(j) == "ssm":
            return lambda p, x: ssm_mod.ssm_prefill(p, x, cfg.d_model, cfg.ssm)
        return lambda p, x: attn.attention_prefill(
            p, x, cfg.attention, positions, _cache_len(cfg, j, max_len),
            window=layer_window(cfg, j))

    def group_body(carry, gparams):
        h, aux = carry
        gcache = {}
        for j in range(period):
            h, aux_j, _, gcache[f"sub{j}"] = layer_apply(
                gparams[f"sub{j}"], h, cfg, j, mixer(j), enc_out=enc_out)
            aux = aux + aux_j
        return (h, aux), gcache

    (x, aux), cache = jax.lax.scan(_remat(group_body, remat),
                                   (x, jnp.zeros((), jnp.float32)), params)
    return x, cache, aux


def stack_decode_step(params: Params, cache: Pytree, x: jnp.ndarray,
                      cache_len: jnp.ndarray, cfg: ModelConfig, *,
                      enc_out: Optional[jnp.ndarray] = None,
                      ) -> Tuple[jnp.ndarray, Pytree]:
    """One-token decode through the whole stack. x: (B, 1, D).

    The stacked cache rides in the layer scan's carry, so a donated cache
    is updated in place: attention layers write their one token into it
    and read their own row where it lies; SSM layers read their small
    state out and write it back whole.
    """
    from repro.runtime import telemetry

    period = cfg.layer_period
    n_groups = cfg.n_layers // period
    n_attn = sum(cfg.layer_kind(j) == "attn" for j in range(period))
    telemetry.count("decode.cache_layers_in_place", n_groups * n_attn)
    telemetry.count("decode.cache_layers_copied", n_groups * (period - n_attn))
    _count_moe_layers(cfg, x)

    def mixer(j, layer, c):
        if cfg.layer_kind(j) == "attn":
            return lambda p, x: attn.attention_decode_step(
                p, x, c, layer, cache_len, cfg.attention,
                window=layer_window(cfg, j))

        def ssm_step(p, x):
            row = jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(
                t, layer, 0, keepdims=False), c)
            out, row = ssm_mod.ssm_decode_step(p, x, row, cfg.d_model, cfg.ssm)
            return out, jax.tree.map(lambda t, r: jax.lax.dynamic_update_index_in_dim(
                t, r, layer, 0), c, row)
        return ssm_step

    def group_body(carry, scanned):
        h, cache = carry
        gparams, layer = scanned
        cache = dict(cache)
        for j in range(period):
            h, _, _, cache[f"sub{j}"] = layer_apply(
                gparams[f"sub{j}"], h, cfg, j, mixer(j, layer, cache[f"sub{j}"]),
                enc_out=enc_out)
        return (h, cache), None

    (x, cache), _ = jax.lax.scan(group_body, (x, cache),
                                 (params, jnp.arange(n_groups)))
    return x, cache
