"""Mamba2 SSD chunked-scan Pallas TPU kernel.

TPU adaptation (DESIGN.md §2): the original SSD CUDA kernel splits work over
SMs with a separate inter-chunk scan kernel. On TPU the grid executes
*sequentially* over the innermost dimension, so the inter-chunk recurrence
folds into the same kernel: the running state (P, N) lives in VMEM scratch
that persists across the chunk grid dimension — a single fused pass, no
second kernel and no HBM round-trip for the states.

Per (batch, head, chunk) tile:
  intra-chunk  : (C @ B^T) ⊙ L  then  @ x      — two MXU matmuls
  inter-chunk  : C @ state                      — one MXU matmul
  state update : state*exp(cum_last) + (x⊙decay)^T @ B

Tile sizes: chunk × N and chunk × P with chunk=128..256, N=128, P=64 — all
MXU-aligned. B/C are group-shared across heads (Mamba2 GQA analogue); the
index_map folds head -> group, so no replication materializes in HBM.

Inputs are pre-scaled by the wrapper (`ops.ssd_scan`): xdt = x*dt and the
chunk-local cumsum of dt * a (a = -exp(a_log)), both head-major so that
every block's last two dims are whole (chunk, P/N) tiles — elementwise prep
stays in XLA where it fuses with the upstream projections.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xdt_ref, cum_ref, b_ref, c_ref, y_ref, fin_ref, state_scr, *,
                n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xdt = xdt_ref[0, 0].astype(jnp.float32)            # (Q, P)
    cum_row = cum_ref[0, 0].astype(jnp.float32)        # (1, Q): cum_j
    bt = b_ref[0, 0].astype(jnp.float32)               # (Q, N)
    ct = c_ref[0, 0].astype(jnp.float32)               # (Q, N)
    q = xdt.shape[0]

    # Mosaic has no cumsum and no (1, Q) -> (Q, 1) relayout: the wrapper
    # passes the chunk-local cumsum as a row, and the columns below are
    # masked lane-reductions of its broadcast.
    def last_lane(n_rows):                             # (n_rows, 1): cum_{Q-1}
        sel = jax.lax.broadcasted_iota(jnp.int32, (n_rows, q), 1) == q - 1
        return jnp.sum(jnp.where(sel, jnp.broadcast_to(cum_row, (n_rows, q)),
                                 0.0), axis=1, keepdims=True)

    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    cum_j = jnp.broadcast_to(cum_row, (q, q))
    cum_col = jnp.sum(jnp.where(rows == cols, cum_j, 0.0), axis=1,
                      keepdims=True)                   # (Q, 1): cum_i
    # L[i, j] = exp(cum_i - cum_j), i >= j  (1-semiseparable mask)
    L = jnp.where(rows >= cols, jnp.exp(cum_col - cum_j), 0.0)

    scores = _dot_t(ct, bt) * L                        # C @ B^T
    y = jnp.dot(scores, xdt, preferred_element_type=jnp.float32)   # (Q, P)

    state = state_scr[...]                             # (P, N)
    # inter-chunk: y += exp(cum) * (C @ state^T)
    y = y + jnp.exp(cum_col) * _dot_t(ct, state)

    decay_to_end = jnp.exp(last_lane(q) - cum_col)     # (Q, 1)
    state_new = state * jnp.exp(last_lane(state.shape[0])) + jax.lax.dot_general(
        xdt * decay_to_end, bt, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (x*decay)^T @ B
    state_scr[...] = state_new

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _final():
        fin_ref[0, 0] = state_new.astype(fin_ref.dtype)


def _dot_t(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a @ b^T without materializing the transpose."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def ssd_scan(xdt: jnp.ndarray, cum: jnp.ndarray, B: jnp.ndarray, C: jnp.ndarray,
             *, chunk: int = 128, interpret: bool = False,
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused SSD scan over head-major operands.

    xdt: (batch, H, S, P)  dt-weighted inputs (x * dt)
    cum: (batch, H, 1, S)  chunk-local inclusive cumsum of the log-decays
                           (dt * a, a negative), restarting every `chunk`
    B:   (batch, G, S, N), C: (batch, G, S, N), G | H.
    Returns (y (batch,H,S,P) fp32, final_state (batch,H,P,N) fp32).
    S must be a multiple of `chunk` (wrapper pads). Every block's last two
    dims are (chunk, P), (1, chunk), (chunk, N) or (P, N): whole array
    dims or multiples of the (8, 128) tile once chunk is a multiple of 128.
    """
    bsz, h, s, p = xdt.shape
    g, n = B.shape[1], B.shape[3]
    assert s % chunk == 0, (s, chunk)
    assert h % g == 0, (h, g)
    rep = h // g
    nc = s // chunk

    kernel = functools.partial(_ssd_kernel, n_chunks=nc)
    y, fin = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci, r=rep: (bi, hi // r, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci, r=rep: (bi, hi // r, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xdt, cum, B, C)
    return y, fin
