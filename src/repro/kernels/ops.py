"""jit'd wrappers around the Pallas kernels, in model-native layouts.

The kernels compile to Mosaic for a TPU. Anywhere else a caller must ask
for the Pallas interpreter with ``interpret=True``; without it the call
fails instead of quietly running interpreted. The wrappers do the layout
transposes + padding and the cheap elementwise prep that XLA fuses with
neighbouring ops.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import skewed_bucket as _sb
from repro.kernels import ssd_scan as _ssd


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """Model layout: q (B, Sq, Hq, D); k/v (B, Sk, Hkv, D) -> (B, Sq, Hq, D)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                              scale=scale, block_q=block_q, block_k=block_k,
                              interpret=interpret)
    return jnp.swapaxes(out, 1, 2)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a_log: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray, *, chunk: int = 128,
             init_state: Optional[jnp.ndarray] = None,
             interpret: bool = False,
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mamba2 SSD scan, same contract as ``ref.ssd_scan_ref``.

    x: (batch, S, H, P); dt: (batch, S, H) (already softplus'd);
    a_log: (H,); B/C: (batch, S, G, N).
    """
    bsz, s, h, p = x.shape
    a = -jnp.exp(a_log.astype(jnp.float32))
    dta = dt.astype(jnp.float32) * a                   # (b, S, H)
    xdt = x.astype(jnp.float32) * dt[..., None]

    c = min(chunk, s)
    pad = (-s) % c
    if pad:
        xdt = jnp.pad(xdt, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dta = jnp.pad(dta, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # head-major operands; the chunk-local cumsum of the log-decays is a
    # (1, S) row per head
    cum = jnp.cumsum(dta.reshape(bsz, -1, c, h), axis=2)
    cum = jnp.transpose(cum.reshape(bsz, s + pad, h), (0, 2, 1))[:, :, None, :]
    y, fin = _ssd.ssd_scan(jnp.transpose(xdt, (0, 2, 1, 3)), cum,
                           jnp.transpose(B, (0, 2, 1, 3)),
                           jnp.transpose(C, (0, 2, 1, 3)),
                           chunk=c, interpret=interpret)
    y = jnp.transpose(y, (0, 2, 1, 3))[:, :s]
    if init_state is not None:
        # fold a nonzero initial state in linearly (the scan is linear in
        # the state): y += exp(cumsum dta) C . init ; final += prod-decay*init
        cum = jnp.cumsum(dta[:, :s], axis=1)           # (b,S,H)
        rep = h // B.shape[2]
        Ch = jnp.repeat(C[:, :s], rep, axis=2).astype(jnp.float32)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bshn,bhpn->bshp", Ch, init_state.astype(jnp.float32))
        fin = fin + init_state * jnp.exp(cum[:, -1])[..., None, None]
    return y.astype(x.dtype), fin


def skewed_bucket(hashes: jnp.ndarray, capacities: jnp.ndarray, *,
                  interpret: bool = False) -> jnp.ndarray:
    """Algorithm 1 bucket map (paper §7). hashes (T,), capacities (E,)."""
    return _sb.skewed_bucket(hashes, capacities, interpret=interpret)
