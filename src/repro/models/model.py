"""Top-level model: embeddings + stack(s) + head, train loss, decode step.

``init_params`` is jit/eval_shape-traceable so the dry-run can build
ShapeDtypeStruct pytrees for 100B+ configs without allocating.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, padded_vocab_size
from repro.models import frontends, transformer
from repro.models.moe import MoEStats
from repro.models.layers import (
    embed, embedding_init, rmsnorm, rmsnorm_init, sinusoidal_positions, unembed,
)

Pytree = Any

NEG_INF = -1e30


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def mask_pad_logits(logits: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Embedding tables are padded to a 256 multiple (sharding divisibility);
    pad-vocab logits are forced to -inf so softmax mass is exact."""
    pv = padded_vocab_size(cfg)
    if pv == cfg.vocab_size:
        return logits
    valid = jnp.arange(pv) < cfg.vocab_size
    return jnp.where(valid, logits, NEG_INF)


def init_params(key, cfg: ModelConfig) -> Pytree:
    ks = jax.random.split(key, 6)
    dt = _dtype(cfg)
    pv = padded_vocab_size(cfg)
    p: Dict[str, Any] = {
        "embed": embedding_init(ks[0], pv, cfg.d_model, dt),
        "stack": transformer.stack_init(ks[1], cfg, cross=cfg.encoder_layers > 0,
                                        dtype=dt),
        "final_norm": rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = embedding_init(ks[2], pv, cfg.d_model, dt)
    if cfg.encoder_layers > 0:
        enc_cfg = _encoder_cfg(cfg)
        p["encoder"] = transformer.stack_init(ks[3], enc_cfg, dtype=dt)
        p["enc_norm"] = rmsnorm_init(cfg.d_model)
    if cfg.frontend != "none":
        p["adapter"] = frontends.adapter_init(ks[4], cfg, dt)
    return p


def params_axes(cfg: ModelConfig) -> Pytree:
    ax: Dict[str, Any] = {
        "embed": {"table": ("vocab", "embed")},
        "stack": transformer.stack_axes(cfg, cross=cfg.encoder_layers > 0),
        "final_norm": {"scale": (None,)},
    }
    if not cfg.tie_embeddings:
        ax["unembed"] = {"table": ("vocab", "embed")}
    if cfg.encoder_layers > 0:
        ax["encoder"] = transformer.stack_axes(_encoder_cfg(cfg))
        ax["enc_norm"] = {"scale": (None,)}
    if cfg.frontend != "none":
        ax["adapter"] = {"w": (None, "embed")}
    return ax


def _encoder_cfg(cfg: ModelConfig) -> ModelConfig:
    import dataclasses
    return dataclasses.replace(cfg, n_layers=cfg.encoder_layers, moe=None,
                               attn_period=0, ssm=None, encoder_layers=0)


def encode(params: Pytree, enc_feats: jnp.ndarray, cfg: ModelConfig, *,
           remat: str = "none") -> jnp.ndarray:
    """Whisper-style encoder over precomputed (stub) frame embeddings."""
    enc_cfg = _encoder_cfg(cfg)
    x = frontends.adapter_apply(params["adapter"], enc_feats) \
        if cfg.frontend != "none" else enc_feats
    s = x.shape[1]
    x = x + sinusoidal_positions(s, cfg.d_model)[None].astype(x.dtype)
    pos = jnp.broadcast_to(jnp.arange(s)[None], x.shape[:2])
    x, _, _ = transformer.stack_apply(params["encoder"], x, enc_cfg, pos,
                                      causal=False, remat=remat)
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def _inputs(params: Pytree, tokens: Optional[jnp.ndarray], cfg: ModelConfig,
            input_embeds: Optional[jnp.ndarray], enc_feats: Optional[jnp.ndarray],
            remat: str) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
    """The decoder stack's input (B,S,D), its positions (B,S) and the
    encoder's output (None without an encoder)."""
    if input_embeds is not None:
        x = frontends.adapter_apply(params["adapter"], input_embeds)
    else:
        x = embed(params["embed"], tokens)
    if cfg.attention is not None and cfg.attention.rope_style == "none" \
            and cfg.encoder_layers > 0:
        # whisper: sinusoidal positions on decoder too
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model)[None].astype(x.dtype)

    b, s = x.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    enc_out = None
    if cfg.encoder_layers > 0:
        assert enc_feats is not None, "enc-dec model requires enc_feats"
        enc_out = encode(params, enc_feats, cfg, remat=remat)
    return x, pos, enc_out


def hidden_states(params: Pytree, tokens: Optional[jnp.ndarray],
                  cfg: ModelConfig, *,
                  input_embeds: Optional[jnp.ndarray] = None,
                  enc_feats: Optional[jnp.ndarray] = None,
                  remat: str = "none", constrain=None,
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, MoEStats]:
    """Final-norm hidden states (B,S,D) (pre-unembed), the moe aux loss and
    the moe dispatch's row counts."""
    x, pos, enc_out = _inputs(params, tokens, cfg, input_embeds, enc_feats,
                              remat)
    if constrain is not None:
        x = constrain(x)
    x, aux, stats = transformer.stack_apply(params["stack"], x, cfg, pos,
                                            enc_out=enc_out, remat=remat,
                                            constrain=constrain)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if constrain is not None:
        x = constrain(x, kind="hidden")
    return x, aux, stats


def forward(params: Pytree, tokens: Optional[jnp.ndarray], cfg: ModelConfig, *,
            input_embeds: Optional[jnp.ndarray] = None,
            enc_feats: Optional[jnp.ndarray] = None,
            remat: str = "none", constrain=None,
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits (B,S,V), moe_aux_loss)."""
    x, aux, _ = hidden_states(params, tokens, cfg, input_embeds=input_embeds,
                              enc_feats=enc_feats, remat=remat,
                              constrain=constrain)
    head = params["unembed"] if "unembed" in params else params["embed"]
    logits = unembed(head, x)
    if constrain is not None:
        logits = constrain(logits, kind="logits")
    return logits, aux


# vocabularies at or above this size use the chunked softmax-xent (the fp32
# logits tensor of a 262k-vocab model is the single largest train buffer)
CHUNKED_XENT_VOCAB = 32_768
XENT_CHUNK = 4_096


def chunked_softmax_xent(x: jnp.ndarray, table: jnp.ndarray,
                         labels: jnp.ndarray, vocab_size: int,
                         chunk: int = XENT_CHUNK) -> jnp.ndarray:
    """Cross-entropy without materializing (B,S,V) logits.

    Scans vocab chunks with an online (max, sumexp, true-logit) carry; the
    per-chunk logits tile (B,S,C) is recomputed in the backward
    (jax.checkpoint), exactly like flash attention treats its probability
    tile. x: (B,S,D); table: (V_padded, D) (pad rows masked via vocab_size).
    Returns per-token nll (B,S) fp32.
    """
    v = table.shape[0]
    nc = -(-v // chunk)
    vp = nc * chunk
    if vp != v:
        table = jnp.pad(table, ((0, vp - v), (0, 0)))
    tchunks = table.reshape(nc, chunk, table.shape[1])

    def step(carry, inp):
        m_p, l_p, t_p = carry
        ci, tc = inp                                   # tc (C, D)
        logits = jnp.einsum("bsd,cd->bsc", x.astype(jnp.float32),
                            tc.astype(jnp.float32))
        gids = ci * chunk + jnp.arange(chunk)          # global vocab ids
        logits = jnp.where(gids[None, None, :] < vocab_size, logits, NEG_INF)
        m_c = jnp.max(logits, axis=-1)
        m_n = jnp.maximum(m_p, m_c)
        l_n = l_p * jnp.exp(m_p - m_n) + jnp.sum(
            jnp.exp(logits - m_n[..., None]), axis=-1)
        t_n = t_p + jnp.sum(
            jnp.where(labels[..., None] == gids[None, None, :], logits, 0.0),
            axis=-1)
        return (m_n, l_n, t_n), None

    step = jax.checkpoint(step, policy=jax.checkpoint_policies.nothing_saveable)
    b, s = labels.shape
    with jax.named_scope("cross_entropy"):
        init = (jnp.full((b, s), NEG_INF, jnp.float32),
                jnp.zeros((b, s), jnp.float32), jnp.zeros((b, s), jnp.float32))
        (m, l, t), _ = jax.lax.scan(step, init, (jnp.arange(nc), tchunks))
        lse = jnp.log(l) + m
        return lse - t


def loss_fn(params: Pytree, batch: Dict[str, jnp.ndarray], cfg: ModelConfig, *,
            remat: str = "none", constrain=None,
            ) -> jnp.ndarray:
    """Next-token cross-entropy (+ MoE aux). batch keys: tokens|input_embeds,
    labels, and enc_feats for enc-dec archs."""
    return loss_and_stats(params, batch, cfg, remat=remat,
                          constrain=constrain)[0]


def loss_and_stats(params: Pytree, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
                   *, remat: str = "none", constrain=None,
                   ) -> Tuple[jnp.ndarray, MoEStats]:
    """``loss_fn``'s loss, and the row counts of its MoE dispatches."""
    labels = batch["labels"]
    x, aux, stats = hidden_states(params, batch.get("tokens"), cfg,
                                  input_embeds=batch.get("input_embeds"),
                                  enc_feats=batch.get("enc_feats"),
                                  remat=remat, constrain=constrain)
    head = params["unembed"] if "unembed" in params else params["embed"]
    if padded_vocab_size(cfg) >= CHUNKED_XENT_VOCAB:
        nll = chunked_softmax_xent(x, head["table"], labels, cfg.vocab_size)
    else:
        logits = unembed(head, x)
        if constrain is not None:
            logits = constrain(logits, kind="logits")
        nll = _dense_xent(mask_pad_logits(logits, cfg), labels)
    mask = batch.get("loss_mask", jnp.ones_like(nll))
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0) + aux, stats


def _dense_xent(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Per-token nll (B,S) fp32 from whole (B,S,V) logits."""
    # Cross-entropy in logsumexp + select-reduce form: every op is
    # elementwise or a reduction along vocab, so GSPMD keeps the logits
    # vocab-sharded end-to-end (partial reductions + a scalar-ish
    # all-reduce) instead of all-gathering a (B,S,V) fp32 tensor for a
    # take_along_axis gather.
    logits_f = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits_f, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(logits_f - m), axis=-1)) + m[..., 0]
    vocab_iota = jnp.arange(logits.shape[-1], dtype=labels.dtype)
    true_logit = jnp.sum(
        jnp.where(labels[..., None] == vocab_iota, logits_f, 0.0), axis=-1)
    return lse - true_logit


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------

def prefill(params: Pytree, tokens: Optional[jnp.ndarray], cfg: ModelConfig,
            max_len: int, *, enc_feats: Optional[jnp.ndarray] = None,
            input_embeds: Optional[jnp.ndarray] = None,
            remat: str = "none",
            ) -> Tuple[jnp.ndarray, Pytree]:
    """Process a prompt batch and build the decode state.

    tokens: (B, S) (or input_embeds (B, S, F) for vision prompts).
    Returns (last-token logits (B, V), decode state with cache filled and
    length = S) — the serving prefill step.
    """
    x, pos, enc_out = _inputs(params, tokens, cfg, input_embeds, enc_feats,
                              remat)
    x, cache, _ = transformer.stack_prefill(params["stack"], x, cfg, pos,
                                            max_len, enc_out=enc_out,
                                            remat=remat)
    x = rmsnorm(params["final_norm"], x[:, -1:, :], cfg.norm_eps)
    head = params["unembed"] if "unembed" in params else params["embed"]
    logits = mask_pad_logits(unembed(head, x)[:, 0, :], cfg)
    state = {"cache": cache, "length": jnp.full((), pos.shape[1], jnp.int32)}
    return logits, state


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int) -> Pytree:
    dt = _dtype(cfg)
    return {
        "cache": transformer.stack_init_cache(cfg, batch, max_len, dtype=dt),
        "length": jnp.zeros((), jnp.int32),
    }


def decode_step(params: Pytree, state: Pytree, token: jnp.ndarray,
                cfg: ModelConfig, *, enc_out: Optional[jnp.ndarray] = None,
                ) -> Tuple[jnp.ndarray, Pytree]:
    """token: (B,) int32. Returns (logits (B,V), new state)."""
    x = embed(params["embed"], token[:, None])
    if cfg.attention is not None and cfg.attention.rope_style == "none" \
            and cfg.encoder_layers > 0:
        # whisper: sinusoidal position for the current step, computed directly
        x = x + _sin_row(state["length"], cfg.d_model).astype(x.dtype)[None, None]

    x, new_cache = transformer.stack_decode_step(
        params["stack"], state["cache"], x, state["length"], cfg, enc_out=enc_out)
    with jax.named_scope("decode_sample"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        head = params["unembed"] if "unembed" in params else params["embed"]
        logits = mask_pad_logits(unembed(head, x)[:, 0, :], cfg)
    return logits, {"cache": new_cache, "length": state["length"] + 1}


def _sin_row(pos: jnp.ndarray, d: int) -> jnp.ndarray:
    import math as _m
    half = d // 2
    inv = jnp.exp(-_m.log(10_000.0) / max(half - 1, 1)
                  * jnp.arange(half, dtype=jnp.float32))
    scaled = pos.astype(jnp.float32) * inv
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)])
