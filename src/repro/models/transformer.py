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

from typing import Any, Dict, Optional, Tuple

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


def _layer_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig, idx: int,
                 positions: jnp.ndarray, *, enc_out: Optional[jnp.ndarray] = None,
                 causal: bool = True, impl: str = "xla", constrain=None,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, moe_mod.MoEStats]:
    """Pre-norm residual layer. Returns (x, moe_aux_loss, moe stats)."""
    kind = cfg.layer_kind(idx)
    aux = jnp.zeros((), jnp.float32)
    stats = moe_mod.MoEStats.zeros()
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if kind == "attn":
        acfg = cfg.attention
        if not causal:
            acfg = attn.AttentionConfig(**{**acfg.__dict__, "causal": False})
        window = None
        if acfg.local_global != (0, 0):
            window = 0 if cfg.layer_is_global_attn(idx) else acfg.sliding_window
        h = attn.attention_apply(p["mixer"], h, acfg, positions,
                                 window_override=window, impl=impl)
    else:
        h = ssm_mod.ssm_apply(p["mixer"], h, cfg.d_model, cfg.ssm, impl=impl,
                              constrain=constrain)
    x = x + h
    if "cross" in p:
        h = rmsnorm(p["norm_cross"], x, cfg.norm_eps)
        h = attn.attention_apply(p["cross"], h, cfg.attention, positions,
                                 kv_source=enc_out, impl="xla")
        x = x + h
    if "ffn" in p:
        h = rmsnorm(p["norm2"], x, cfg.norm_eps)
        if cfg.layer_is_moe(idx):
            h, aux, stats = moe_mod.moe_layer(p["ffn"], h, cfg.moe, cfg.act,
                                              constrain=constrain)
        else:
            h = mlp_apply(p["ffn"], h, cfg.act)
        x = x + h
    return x, aux, stats


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
                causal: bool = True, impl: str = "xla", remat: str = "none",
                constrain=None,
                ) -> Tuple[jnp.ndarray, jnp.ndarray, moe_mod.MoEStats]:
    """Returns (x, moe aux loss, moe stats summed over layers).

    constrain: optional h -> h sharding hook applied to the residual
    stream at group boundaries (sequence-parallel saved activations)."""
    period = cfg.layer_period
    _count_moe_layers(cfg, x)

    def group_body(carry, gparams):
        h, aux, stats = carry
        for j in range(period):
            h, aux_j, stats_j = _layer_apply(gparams[f"sub{j}"], h, cfg, j,
                                             positions, enc_out=enc_out,
                                             causal=causal, impl=impl,
                                             constrain=constrain)
            aux, stats = aux + aux_j, stats.plus(stats_j)
        if constrain is not None:
            h = constrain(h)
        return (h, aux, stats), None

    if remat == "full":
        group_body = jax.checkpoint(group_body,
                                    policy=jax.checkpoint_policies.nothing_saveable)
    elif remat == "dots":
        group_body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    (x, aux, stats), _ = jax.lax.scan(
        group_body, (x, jnp.zeros((), jnp.float32), moe_mod.MoEStats.zeros()),
        params)
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
    acfg = cfg.attention
    if acfg.local_global != (0, 0) and not cfg.layer_is_global_attn(j):
        return min(max_len, acfg.sliding_window)
    if acfg.sliding_window > 0 and acfg.local_global == (0, 0):
        return min(max_len, acfg.sliding_window)
    return max_len


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
                  enc_out: Optional[jnp.ndarray] = None, impl: str = "xla",
                  remat: str = "none",
                  ) -> Tuple[jnp.ndarray, Pytree, jnp.ndarray]:
    """Full-sequence pass that also builds the decode cache.

    Returns (hidden (B,S,D), cache pytree matching stack_init_cache(max_len),
    moe aux loss). Cache slots follow the decode ring-buffer layout so
    stack_decode_step continues seamlessly with cache_len = S.
    """
    period = cfg.layer_period
    _count_moe_layers(cfg, x)

    def group_body(carry, gparams):
        h, aux = carry
        gcache = {}
        for j in range(period):
            p = gparams[f"sub{j}"]
            kind = cfg.layer_kind(j)
            hin = rmsnorm(p["norm1"], h, cfg.norm_eps)
            if kind == "attn":
                acfg = cfg.attention
                window = None
                if acfg.local_global != (0, 0):
                    window = 0 if cfg.layer_is_global_attn(j) else acfg.sliding_window
                out, c = attn.attention_prefill(p["mixer"], hin, acfg, positions,
                                                _cache_len(cfg, j, max_len),
                                                window_override=window, impl=impl)
            else:
                out, c = ssm_mod.ssm_prefill(p["mixer"], hin, cfg.d_model,
                                             cfg.ssm, impl=impl)
            h = h + out
            if "cross" in p:
                hin = rmsnorm(p["norm_cross"], h, cfg.norm_eps)
                out = attn.attention_apply(p["cross"], hin, cfg.attention,
                                           positions, kv_source=enc_out,
                                           impl="xla")
                h = h + out
            if "ffn" in p:
                hin = rmsnorm(p["norm2"], h, cfg.norm_eps)
                if cfg.layer_is_moe(j):
                    out, aux_j, _ = moe_mod.moe_layer(p["ffn"], hin, cfg.moe, cfg.act)
                    aux = aux + aux_j
                else:
                    out = mlp_apply(p["ffn"], hin, cfg.act)
                h = h + out
            gcache[f"sub{j}"] = c
        return (h, aux), gcache

    if remat == "full":
        group_body = jax.checkpoint(group_body,
                                    policy=jax.checkpoint_policies.nothing_saveable)
    elif remat == "dots":
        group_body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    (x, aux), cache = jax.lax.scan(group_body,
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

    def group_body(carry, scanned):
        h, cache = carry
        gparams, layer = scanned
        cache = dict(cache)
        for j in range(period):
            p, c = gparams[f"sub{j}"], cache[f"sub{j}"]
            hin = rmsnorm(p["norm1"], h, cfg.norm_eps)
            if cfg.layer_kind(j) == "attn":
                acfg = cfg.attention
                window = None
                if acfg.local_global != (0, 0):
                    window = 0 if cfg.layer_is_global_attn(j) else acfg.sliding_window
                out, c = attn.attention_decode_step(p["mixer"], hin, c, layer,
                                                    cache_len, acfg,
                                                    window_override=window)
            else:
                row = jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(
                    t, layer, 0, keepdims=False), c)
                out, row = ssm_mod.ssm_decode_step(p["mixer"], hin, row,
                                                   cfg.d_model, cfg.ssm)
                c = jax.tree.map(lambda t, r: jax.lax.dynamic_update_index_in_dim(
                    t, r, layer, 0), c, row)
            h = h + out
            if "cross" in p:
                hin = rmsnorm(p["norm_cross"], h, cfg.norm_eps)
                h = h + attn.attention_apply(p["cross"], hin, cfg.attention,
                                             None, kv_source=enc_out)
            if "ffn" in p:
                hin = rmsnorm(p["norm2"], h, cfg.norm_eps)
                if cfg.layer_is_moe(j):
                    out, _, _ = moe_mod.moe_layer(p["ffn"], hin, cfg.moe, cfg.act)
                else:
                    out = mlp_apply(p["ffn"], hin, cfg.act)
                h = h + out
            cache[f"sub{j}"] = c
        return (h, cache), None

    (x, cache), _ = jax.lax.scan(group_body, (x, cache),
                                 (params, jnp.arange(n_groups)))
    return x, cache
