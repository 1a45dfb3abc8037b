"""Mamba2 block (SSD — state-space duality, arXiv:2405.21060).

Chunked SSD algorithm: within-chunk term is a (masked) attention-like dense
matmul; across chunks a small recurrence over per-chunk states. This is the
path training and prefill take; ``repro.kernels.ssd_scan`` is a
standalone Pallas kernel of the same math that no model calls.

Layout follows the Mamba2 paper: d_inner = expand*d_model split into heads of
size P=head_dim; per-head scalar decay a_t = exp(dt*A); B/C shared across
heads within a group (n_groups, like GQA).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.models.layers import Params, _dense_init, rmsnorm, rmsnorm_init


def ssm_dims(d_model: int, cfg: SSMConfig) -> Dict[str, int]:
    d_inner = cfg.expand * d_model
    n_heads = d_inner // cfg.head_dim
    return dict(d_inner=d_inner, n_heads=n_heads, d_state=cfg.state_dim,
                n_groups=cfg.n_groups, conv_dim=d_inner + 2 * cfg.n_groups * cfg.state_dim)


def ssm_init(key, d_model: int, cfg: SSMConfig, dtype=jnp.bfloat16) -> Params:
    dims = ssm_dims(d_model, cfg)
    d_in, nh, ds, ng = dims["d_inner"], dims["n_heads"], dims["d_state"], dims["n_groups"]
    ks = jax.random.split(key, 4)
    in_dim = 2 * d_in + 2 * ng * ds + nh  # [z, x, B, C, dt]
    return {
        "w_in": _dense_init(ks[0], d_model, in_dim, dtype=dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.conv_width, dims["conv_dim"]),
                                     jnp.float32) / math.sqrt(cfg.conv_width)
                   ).astype(dtype),
        "conv_b": jnp.zeros((dims["conv_dim"],), jnp.float32),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "gate_norm": rmsnorm_init(d_in),
        "w_out": _dense_init(ks[2], d_in, d_model, dtype=dtype),
    }


def _split_proj(proj: jnp.ndarray, d_model: int, cfg: SSMConfig):
    dims = ssm_dims(d_model, cfg)
    d_in, nh, ds, ng = dims["d_inner"], dims["n_heads"], dims["d_state"], dims["n_groups"]
    z, xbc_dt = jnp.split(proj, [d_in], axis=-1)
    xbc, dt = jnp.split(xbc_dt, [d_in + 2 * ng * ds], axis=-1)
    return z, xbc, dt


def _causal_conv(xbc: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv over sequence. xbc: (B, S, C); w: (W, C)."""
    width = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(pad[:, i: i + xbc.shape[1], :] * w[i] for i in range(width))
    return jax.nn.silu(out + b.astype(out.dtype))


def ssd_scan_chunks(x: jnp.ndarray, dt: jnp.ndarray, a_log: jnp.ndarray,
                    B: jnp.ndarray, C: jnp.ndarray, chunk: int,
                    init_state: jnp.ndarray = None, constrain=None,
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SSD with the chunk axis *scanned* (one chunk's intra tensors live at
    a time) instead of batched — the memory-lean XLA lowering for long
    sequences; same math as `ssd_chunked`. The Pallas kernel streams chunks
    the same way (its VMEM state scratch is this scan's carry)."""
    bsz, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    nc = s // chunk
    assert s % chunk == 0, (s, chunk)
    rep = h // g

    a = -jnp.exp(a_log.astype(jnp.float32))
    dta = (dt * a).reshape(bsz, nc, chunk, h)
    # intra-chunk matmul operands follow the model compute dtype (bf16 on
    # the bf16 path) with fp32 accumulation — halves the scan-saved VJP
    # residual stacks, the decays/cumsums stay fp32 (EXPERIMENTS cell B4)
    cdt = x.dtype if x.dtype == jnp.bfloat16 else jnp.float32
    xw = (x.astype(jnp.float32) * dt[..., None]).astype(cdt).reshape(
        bsz, nc, chunk, h, p)
    Bc = B.astype(cdt).reshape(bsz, nc, chunk, g, n)
    Cc = C.astype(cdt).reshape(bsz, nc, chunk, g, n)
    if init_state is None:
        init_state = jnp.zeros((bsz, h, p, n), jnp.float32)

    mask = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(state, inp):
        dc, xc, bc, cc = inp            # (b,chunk,h), (b,chunk,h,p), (b,chunk,g,n)
        bch = jnp.repeat(bc, rep, axis=2)
        cch = jnp.repeat(cc, rep, axis=2)
        cum = jnp.cumsum(dc, axis=1)                       # (b,q,h)
        li = cum[:, :, None, :] - cum[:, None, :, :]       # (b,q,k,h)
        L = jnp.where(mask[None, :, :, None], jnp.exp(li), 0.0)
        scores = jnp.einsum("bqhn,bkhn->bqkh", cch, bch,
                            preferred_element_type=jnp.float32) * L
        y = jnp.einsum("bqkh,bkhp->bqhp", scores.astype(cdt), xc,
                       preferred_element_type=jnp.float32)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bqhn,bhpn->bqhp", cch.astype(jnp.float32), state)
        decay_end = jnp.exp(cum[:, -1:, :] - cum)          # (b,q,h)
        state = state * jnp.exp(cum[:, -1])[:, :, None, None] + jnp.einsum(
            "bqhn,bqhp,bqh->bhpn", bch.astype(jnp.float32),
            xc.astype(jnp.float32), decay_end)
        if constrain is not None:
            # keep the carried (and scan-saved) state head-sharded — the
            # saved-state stack is (n_chunks, B, H, P, N), the dominant
            # train-time buffer for big hybrid models (jamba)
            state = constrain(state, kind="ssm_state")
        return state, y

    # recompute the per-chunk score tile in the VJP instead of stacking all
    # (q x q x H) tiles across chunks (same trick as chunked_attention)
    step = jax.checkpoint(step, policy=jax.checkpoint_policies.nothing_saveable)
    xs = (jnp.moveaxis(dta, 1, 0), jnp.moveaxis(xw, 1, 0),
          jnp.moveaxis(Bc, 1, 0), jnp.moveaxis(Cc, 1, 0))
    final, ys = jax.lax.scan(step, init_state, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, s, h, p)
    return y, final


# sequences at or above this length scan chunks instead of batching them
SSD_SCAN_THRESHOLD = 4096


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, a_log: jnp.ndarray,
                B: jnp.ndarray, C: jnp.ndarray, chunk: int,
                init_state: jnp.ndarray = None, constrain=None,
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SSD scan.

    x:  (batch, S, H, P)   per-head inputs
    dt: (batch, S, H)      softplus'd step sizes
    B:  (batch, S, G, N), C: (batch, S, G, N); heads are grouped G|H
    Returns (y (batch,S,H,P), final_state (batch,H,P,N)).
    """
    s0 = x.shape[1]
    pad = (-s0) % chunk
    if pad:
        # zero-dt padding is inert: decay exp(0*a)=1, input dt*x=0
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        y, fin = ssd_chunked(x, dt, a_log, B, C, chunk, init_state, constrain)
        return y[:, :s0], fin
    if x.shape[1] >= SSD_SCAN_THRESHOLD:
        return ssd_scan_chunks(x, dt, a_log, B, C, chunk, init_state,
                               constrain)
    bsz, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    nc = s // chunk
    assert s % chunk == 0, f"seq {s} not divisible by chunk {chunk}"
    rep = h // g

    a = -jnp.exp(a_log.astype(jnp.float32))              # (H,) negative
    dta = dt * a                                          # (B, S, H) log-decay
    xw = x * dt[..., None]                                # dt-weighted input

    # reshape into chunks
    xc = xw.reshape(bsz, nc, chunk, h, p)
    dc = dta.reshape(bsz, nc, chunk, h)
    Bc = jnp.repeat(B.reshape(bsz, nc, chunk, g, n), rep, axis=3)  # (b,nc,q,H,N)
    Cc = jnp.repeat(C.reshape(bsz, nc, chunk, g, n), rep, axis=3)

    cum = jnp.cumsum(dc, axis=2)                          # (b, nc, q, H)

    # ---- intra-chunk (dual / attention-like) ------------------------------
    # L[i,j] = exp(cum_i - cum_j) for i >= j
    li = cum[:, :, :, None, :] - cum[:, :, None, :, :]    # (b,nc,q,q,H)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.exp(jnp.where(mask[None, None, :, :, None], li, -jnp.inf))
    scores = jnp.einsum("bcqhn,bckhn->bcqkh", Cc.astype(jnp.float32),
                        Bc.astype(jnp.float32)) * L
    y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", scores, xc.astype(jnp.float32))

    # ---- chunk states ------------------------------------------------------
    # state_c = sum_j exp(cum_last - cum_j) * B_j x_j^T
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)       # (b,nc,q,H)
    states = jnp.einsum("bcqhn,bcqhp,bcqh->bchpn",
                        Bc.astype(jnp.float32), xc.astype(jnp.float32),
                        decay_to_end)                     # (b,nc,H,P,N)
    chunk_decay = jnp.exp(cum[:, :, -1, :])               # (b,nc,H)

    # ---- inter-chunk recurrence (scan over chunks) -------------------------
    if init_state is None:
        init_state = jnp.zeros((bsz, h, p, n), jnp.float32)

    def step(carry, inp):
        st_prev = carry
        st_c, dec_c = inp
        st_new = st_prev * dec_c[:, :, None, None] + st_c
        return st_new, st_prev

    states_t = jnp.moveaxis(states, 1, 0)                 # (nc, b, H, P, N)
    decay_t = jnp.moveaxis(chunk_decay, 1, 0)             # (nc, b, H)
    final, prev_states = jax.lax.scan(step, init_state, (states_t, decay_t))
    prev_states = jnp.moveaxis(prev_states, 0, 1)         # (b, nc, H, P, N)

    # ---- inter-chunk contribution ------------------------------------------
    decay_from_start = jnp.exp(cum)                       # (b,nc,q,H)
    y_inter = jnp.einsum("bcqhn,bchpn,bcqh->bcqhp",
                         Cc.astype(jnp.float32), prev_states, decay_from_start)

    y = (y_intra + y_inter).reshape(bsz, s, h, p)
    return y, final


def _ssm_body(params: Params, x: jnp.ndarray, d_model: int, cfg: SSMConfig,
              constrain=None,
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full-sequence Mamba2 block: projection, conv, SSD scan and gate.
    Returns (out (B, S, D), the raw pre-conv xbc rows, final SSD state)."""
    dims = ssm_dims(d_model, cfg)
    d_in, nh, ds, ng = dims["d_inner"], dims["n_heads"], dims["d_state"], dims["n_groups"]
    bsz, s, _ = x.shape

    proj = x @ params["w_in"]
    z, xbc_raw, dt = _split_proj(proj, d_model, cfg)
    xbc = _causal_conv(xbc_raw, params["conv_w"], params["conv_b"])
    xs, B, C = jnp.split(xbc, [d_in, d_in + ng * ds], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    xs = xs.reshape(bsz, s, nh, cfg.head_dim)
    B = B.reshape(bsz, s, ng, ds)
    C = C.reshape(bsz, s, ng, ds)

    y, final = ssd_chunked(xs, dt, params["a_log"], B, C, min(cfg.chunk, s),
                           constrain=constrain)
    y = y + xs.astype(jnp.float32) * params["d_skip"][:, None]
    y = y.reshape(bsz, s, d_in).astype(x.dtype)
    y = rmsnorm(params["gate_norm"], y * jax.nn.silu(z))
    return y @ params["w_out"], xbc_raw, final


def ssm_apply(params: Params, x: jnp.ndarray, d_model: int, cfg: SSMConfig,
              constrain=None) -> jnp.ndarray:
    """Full-sequence Mamba2 block. x: (B, S, D) -> (B, S, D)."""
    return _ssm_body(params, x, d_model, cfg, constrain)[0]


def ssm_prefill(params: Params, x: jnp.ndarray, d_model: int, cfg: SSMConfig,
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full-sequence Mamba2 that also emits the decode cache
    (conv tail = last conv_width-1 *raw* xbc rows, and the final SSD state)."""
    out, xbc_raw, final = _ssm_body(params, x, d_model, cfg)
    # conv tail: last W-1 raw xbc rows (zero-padded on the left if s < W-1)
    w1 = cfg.conv_width - 1
    pad = jnp.pad(xbc_raw, ((0, 0), (w1, 0), (0, 0)))
    tail = jax.lax.dynamic_slice_in_dim(pad, x.shape[1], w1, axis=1)
    return out, {"conv": tail.astype(x.dtype), "state": final}


# --------------------------------------------------------------------------
# decode (single-token recurrence)
# --------------------------------------------------------------------------

def init_ssm_cache(batch: int, d_model: int, cfg: SSMConfig,
                   dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    dims = ssm_dims(d_model, cfg)
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, dims["conv_dim"]), dtype),
        "state": jnp.zeros((batch, dims["n_heads"], cfg.head_dim, dims["d_state"]),
                           jnp.float32),
    }


def ssm_decode_step(params: Params, x: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                    d_model: int, cfg: SSMConfig,
                    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, 1, D). Single-step SSM recurrence: s' = a*s + dt*B x^T."""
    dims = ssm_dims(d_model, cfg)
    d_in, nh, ds, ng = dims["d_inner"], dims["n_heads"], dims["d_state"], dims["n_groups"]
    bsz = x.shape[0]

    proj = x[:, 0, :] @ params["w_in"]
    z, xbc, dt = _split_proj(proj, d_model, cfg)

    # conv cache: window of last (W-1) inputs
    conv_in = jnp.concatenate([cache["conv"], xbc[:, None, :]], axis=1)  # (B,W,C)
    w = params["conv_w"]
    conv_out = jnp.einsum("bwc,wc->bc", conv_in.astype(jnp.float32),
                          w.astype(jnp.float32)) + params["conv_b"]
    xbc_act = jax.nn.silu(conv_out).astype(x.dtype)
    new_conv = conv_in[:, 1:, :]

    xs, B, C = jnp.split(xbc_act, [d_in, d_in + ng * ds], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])   # (B, H)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    decay = jnp.exp(dt * a)                                            # (B, H)

    xs = xs.reshape(bsz, nh, cfg.head_dim).astype(jnp.float32)
    rep = nh // ng
    Bh = jnp.repeat(B.reshape(bsz, ng, ds), rep, axis=1).astype(jnp.float32)
    Ch = jnp.repeat(C.reshape(bsz, ng, ds), rep, axis=1).astype(jnp.float32)

    dx = xs * dt[..., None]                                            # (B,H,P)
    state = cache["state"] * decay[..., None, None] + \
        jnp.einsum("bhp,bhn->bhpn", dx, Bh)
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch) + xs * params["d_skip"][:, None]
    y = y.reshape(bsz, d_in).astype(x.dtype)

    y = rmsnorm(params["gate_norm"], y * jax.nn.silu(z))
    out = (y @ params["w_out"])[:, None, :]
    return out, {"conv": new_conv, "state": state}
