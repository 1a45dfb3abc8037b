"""Attention: GQA, causal / sliding-window masks, cross-attention, KV cache.

The XLA path (`dot_product_attention`) is the default for lowering/dry-run;
`repro.kernels.ops.flash_attention` provides the Pallas TPU kernel for the
same math (selected via ``impl='pallas'``).
"""
from __future__ import annotations

import functools
import itertools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.configs.base import AttentionConfig
from repro.models.layers import Params, _dense_init, apply_rope

NEG_INF = -1e30


def attention_init(key, d_model: int, cfg: AttentionConfig,
                   dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense_init(ks[0], d_model, cfg.n_heads * cfg.head_dim, dtype=dtype),
        "wk": _dense_init(ks[1], d_model, cfg.n_kv_heads * cfg.head_dim, dtype=dtype),
        "wv": _dense_init(ks[2], d_model, cfg.n_kv_heads * cfg.head_dim, dtype=dtype),
        "wo": _dense_init(ks[3], cfg.n_heads * cfg.head_dim, d_model, dtype=dtype),
    }


def _mask_bias(q_pos: jnp.ndarray, k_pos: jnp.ndarray, causal: bool,
               window: int) -> jnp.ndarray:
    """(..., Sq, Sk) additive bias. window>0 limits lookback (sliding window)."""
    rel = q_pos[..., :, None] - k_pos[..., None, :]
    ok = jnp.ones(rel.shape, bool)
    if causal:
        ok &= rel >= 0
    if window > 0:
        ok &= rel < window
    return jnp.where(ok, 0.0, NEG_INF)


def dot_product_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          bias: Optional[jnp.ndarray], scale: float) -> jnp.ndarray:
    """q: (B, Sq, Hq, Dh); k/v: (B, Sk, Hkv, Dh). GQA via head grouping."""
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    with jax.named_scope("attention_core"):
        qg = q.reshape(b, sq, hkv, group, dh)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        if bias is not None:
            logits = logits + bias[:, None, None, :, :]
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
        return out.reshape(b, sq, hq, dh)


def _open_key_blocks(i: int, bq: int, bk: int, nk: int, sq: int, *,
                     causal: bool, window: int) -> Tuple[int, int]:
    """Key blocks ``[lo, hi)`` that hold a position some query of query
    block ``i`` may see. Query ``r`` sees keys ``r - window < p <= r`` (each
    bound where it applies), so the keys open to the block's rows
    ``first..last`` form one run, from ``first - window + 1`` to ``last``:
    every key block between the first and the last open one is open too."""
    first, last = i * bq, min((i + 1) * bq, sq) - 1
    lo = max(0, (first - window + 1) // bk) if window > 0 else 0
    hi = min(nk, last // bk + 1) if causal else nk
    return lo, max(lo, hi)


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool, window: int, scale: float,
                      block_q: int = 512, block_k: int = 1024) -> jnp.ndarray:
    """Flash-equivalent streaming attention in pure XLA (lax.scan online
    softmax) — the compile target for long sequences where the dense
    (Sq x Sk) logits tensor must never materialize. Same math as
    ``dot_product_attention`` with arange positions; the Pallas kernel
    (`repro.kernels.flash_attention`) is the TPU-native twin.

    Only the (query block, key block) tiles in which the mask leaves some
    position open are computed, forward and backward: each query block
    scans just the run of key blocks that ``_open_key_blocks`` gives it.
    A closed tile changes nothing: after a row's first open key it adds
    exact zeros (``p = 0``, ``alpha = 1``), and what it adds before that
    key is scaled away there (``alpha = 0``). So the result is the same
    arithmetic on the tiles computed. The schedule is static; tracing
    counts ``attention.tiles_computed`` and ``attention.tiles_skipped``.

    q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D) -> (B, Sq, Hq, D).
    """
    from repro.runtime import telemetry

    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    sq_p = -(-sq // bq) * bq
    sk_p = -(-sk // bk) * bk
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    nq, nk = sq_p // bq, sk_p // bk
    # (nq, B, Hkv, g, bq, D) / (nk, B, Hkv, bk, D)
    qb = q.reshape(b, nq, bq, hkv, g, d).transpose(1, 0, 3, 4, 2, 5)
    kb = k.reshape(b, nk, bk, hkv, d).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(b, nk, bk, hkv, d).transpose(1, 0, 3, 2, 4)

    spans = [_open_key_blocks(i, bq, bk, nk, sq, causal=causal, window=window)
             for i in range(nq)]
    computed = sum(hi - lo for lo, hi in spans)
    telemetry.count("attention.tiles_computed", computed)
    telemetry.count("attention.tiles_skipped", nq * nk - computed)

    def q_block(args, n_kv):
        qi, lo, qt = args                                 # qt (B,Hkv,g,bq,D)
        q0 = qi * bq

        def kv_step(carry, inp):
            m_p, l_p, acc = carry
            ki, kt, vt = inp                              # kt (B,Hkv,bk,D)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qt.astype(jnp.float32),
                           kt.astype(jnp.float32)) * scale
            qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            rel = qpos - kpos
            ok = kpos < sk
            if causal:
                ok &= rel >= 0
            if window > 0:
                ok &= rel < window
            s = jnp.where(ok[None, None, None], s, NEG_INF)
            m_c = jnp.max(s, axis=-1, keepdims=True)
            m_n = jnp.maximum(m_p, m_c)
            p = jnp.exp(s - m_n)
            alpha = jnp.exp(m_p - m_n)
            l_n = l_p * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum("bhgqk,bhkd->bhgqd", p,
                                           vt.astype(jnp.float32))
            return (m_n, l_n, acc), None

        # flash-style backward: the (bq, bk) probability tile is REcomputed
        # in the VJP instead of saved per step — without these checkpoints
        # the scan/map VJPs stack all S^2 tiles (the whole point of flash
        # attention is to never materialize that)
        kv_step = jax.checkpoint(
            kv_step, policy=jax.checkpoint_policies.nothing_saveable)
        init = (jnp.full((b, hkv, g, bq, 1), NEG_INF, jnp.float32),
                jnp.zeros((b, hkv, g, bq, 1), jnp.float32),
                jnp.zeros((b, hkv, g, bq, d), jnp.float32))
        kv = (lo + jnp.arange(n_kv), jax.lax.dynamic_slice_in_dim(kb, lo, n_kv),
              jax.lax.dynamic_slice_in_dim(vb, lo, n_kv))
        (m, l, acc), _ = jax.lax.scan(kv_step, init, kv)
        # in q's dtype here: the groups' outputs are copied once more by
        # the concatenation below
        return (acc / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)

    # consecutive query blocks that scan equally many key blocks share one
    # lax.map: a causal mask makes one map per length, no mask a single map
    outs = []
    with jax.named_scope("attention_core"):
        for n_kv, group in itertools.groupby(
                range(nq), key=lambda i: spans[i][1] - spans[i][0]):
            idx = list(group)
            i0, i1 = idx[0], idx[-1] + 1
            fn = jax.checkpoint(functools.partial(q_block, n_kv=n_kv),
                                policy=jax.checkpoint_policies.nothing_saveable)
            lo = jnp.array([spans[i][0] for i in idx], jnp.int32)
            outs.append(jax.lax.map(fn, (jnp.arange(i0, i1), lo, qb[i0:i1])))
    out = jnp.concatenate(outs)                           # (nq,B,Hkv,g,bq,D)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq_p, hq, d)
    return out[:, :sq]


# sequences at or above this length stream through chunked_attention
CHUNKED_THRESHOLD = 2048


def attention_apply(params: Params, x: jnp.ndarray, cfg: AttentionConfig,
                    positions: jnp.ndarray, *, window_override: Optional[int] = None,
                    kv_source: Optional[jnp.ndarray] = None,
                    impl: str = "xla") -> jnp.ndarray:
    """Full-sequence attention (train / prefill).

    kv_source: if given, keys/values come from it (cross-attention, no mask,
    no rope on kv beyond source positions).
    """
    b, s, _ = x.shape
    dh, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = (x @ params["wq"]).reshape(b, s, hq, dh)

    cross = kv_source is not None
    src = kv_source if cross else x
    sk = src.shape[1]
    k = (src @ params["wk"]).reshape(b, sk, hkv, dh)
    v = (src @ params["wv"]).reshape(b, sk, hkv, dh)

    if not cross:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_style)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_style)
        window = cfg.sliding_window if window_override is None else window_override
        bias = _mask_bias(positions, positions, cfg.causal, window)
    else:
        bias = None

    scale = cfg.scale if cfg.scale is not None else 1.0 / math.sqrt(dh)
    if impl == "pallas" and not cross:
        from repro.kernels import ops as kops
        window = cfg.sliding_window if window_override is None else window_override
        out = kops.flash_attention(q, k, v, causal=cfg.causal, window=window,
                                   scale=scale)
    elif not cross and (impl == "chunked" or max(s, sk) >= CHUNKED_THRESHOLD):
        window = cfg.sliding_window if window_override is None else window_override
        out = chunked_attention(q, k, v, causal=cfg.causal, window=window,
                                scale=scale)
    else:
        out = dot_product_attention(q, k, v, bias, scale)
    return out.reshape(b, s, hq * dh) @ params["wo"]


def attention_prefill(params: Params, x: jnp.ndarray, cfg: AttentionConfig,
                      positions: jnp.ndarray, cache_len: int, *,
                      window_override: Optional[int] = None, impl: str = "xla",
                      ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full-sequence self-attention that also emits the decode KV cache.

    Returns (out (B,S,D), cache {"k","v"} of (B, cache_len, Hkv, Dh)) laid
    out ring-buffer style: slot i holds the largest position p < S with
    p % cache_len == i (matches attention_decode_step's addressing).
    """
    b, s, _ = x.shape
    dh, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = (x @ params["wq"]).reshape(b, s, hq, dh)
    k = (x @ params["wk"]).reshape(b, s, hkv, dh)
    v = (x @ params["wv"]).reshape(b, s, hkv, dh)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_style)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_style)

    window = cfg.sliding_window if window_override is None else window_override
    scale = cfg.scale if cfg.scale is not None else 1.0 / math.sqrt(dh)
    if impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=cfg.causal, window=window,
                                   scale=scale)
    elif impl == "chunked" or s >= CHUNKED_THRESHOLD:
        out = chunked_attention(q, k, v, causal=cfg.causal, window=window,
                                scale=scale)
    else:
        bias = _mask_bias(positions, positions, cfg.causal, window)
        out = dot_product_attention(q, k, v, bias, scale)
    out = out.reshape(b, s, hq * dh) @ params["wo"]

    # ring-layout fill: slot i <- position p = s-1 - ((s-1-i) mod cap), p>=0
    cap = cache_len
    idx = jnp.arange(cap)
    src = (s - 1) - jnp.mod((s - 1) - idx, cap)
    valid = src >= 0
    srcc = jnp.clip(src, 0, s - 1)

    def ring(t):                                          # -> (B, Hkv, cap, Dh)
        t = jnp.where(valid[None, :, None, None], jnp.take(t, srcc, axis=1), 0)
        return t.transpose(0, 2, 1, 3).astype(x.dtype)

    return out, {"k": ring(k), "v": ring(v)}


# --------------------------------------------------------------------------
# KV-cache decode
# --------------------------------------------------------------------------

def init_kv_cache(batch: int, max_len: int, cfg: AttentionConfig,
                  dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """Head-major K and V, (B, Hkv, max_len, Dh): the layout both decode
    dots read, so a layer's cache is used where it lies."""
    shape = (batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attention_decode_step(params: Params, x: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                          layer: jnp.ndarray, cache_len: jnp.ndarray,
                          cfg: AttentionConfig, *,
                          window_override: Optional[int] = None,
                          ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One-token decode. x: (B, 1, D); cache_len: scalar int32 (current length).

    ``cache`` is the stacked cache of every layer of this kind, each of
    {"k", "v"} (n_layers, B, Hkv, cap, Dh); this step writes its one token
    into row ``layer`` in place and reads that row where it lies. Each row
    is a ring buffer of ``cap`` slots; sliding-window layers are allocated
    at window size, so wrap-around implements eviction for free.
    """
    b, one, _ = x.shape
    assert one == 1
    dh, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    cap = cache["k"].shape[3]

    pos = jnp.full((b, 1), cache_len, jnp.int32)
    q = (x @ params["wq"]).reshape(b, 1, hq, dh)
    q = apply_rope(q, pos, cfg.rope_theta, cfg.rope_style)
    k_new = (x @ params["wk"]).reshape(b, 1, hkv, dh)
    k_new = apply_rope(k_new, pos, cfg.rope_theta, cfg.rope_style)
    v_new = (x @ params["wv"]).reshape(b, 1, hkv, dh)

    # The token is written in the cache's own (row-major) layout. Without
    # the constraint XLA gives V's write the projection's layout and
    # relayouts the whole stack on entry and exit of the layer scan.
    slot = jnp.mod(cache_len, cap)
    row_layout = Layout(major_to_minor=(0, 1, 2, 3, 4))
    with jax.named_scope("kv_cache_update"):
        def write(stack, new):                    # new (B, 1, Hkv, Dh)
            row = with_layout_constraint(new.transpose(0, 2, 1, 3)[None],
                                         row_layout)
            return jax.lax.dynamic_update_slice(stack, row,
                                                (layer, 0, 0, slot, 0))
        cache = {"k": write(cache["k"], k_new), "v": write(cache["v"], v_new)}
    k = jax.lax.dynamic_index_in_dim(cache["k"], layer, 0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(cache["v"], layer, 0, keepdims=False)

    # Ring buffer: absolute position stored at slot i is the largest p <= L
    # with p % cap == i, i.e. abs(i) = L - ((L - i) mod cap); L = cache_len
    # (the just-inserted token's position).
    idx = jnp.arange(cap)
    abs_pos = cache_len - jnp.mod(cache_len - idx, cap)
    valid = abs_pos >= 0
    window = cfg.sliding_window if window_override is None else window_override
    if window > 0:
        valid &= (cache_len - abs_pos) < window
    bias = jnp.where(valid, 0.0, NEG_INF)                 # (cap,)

    scale = cfg.scale if cfg.scale is not None else 1.0 / math.sqrt(dh)
    with jax.named_scope("attention_core"):
        qg = q.reshape(b, hkv, hq // hkv, dh)
        logits = jnp.einsum("bhgd,bhkd->bhgk", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale + bias
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgk,bhkd->bhgd", probs.astype(v.dtype), v)
    out = out.reshape(b, 1, hq * dh) @ params["wo"]
    return out, cache
