"""Paper Algorithm 1 (skewed hash partitioner) as a Pallas TPU kernel.

bucket(r) = #( inclusive-prefix-sums(capacities) <= hash(r) mod sum(caps) )

Used on the shuffle/dispatch hot path (MoE token -> expert-shard routing,
data-shuffle re-bucketing). The capacities vector is tiny (#executors /
#experts), so every grid step keeps the whole prefix-sum array resident in
VMEM and streams hash tiles through; the bucket search is a broadcast
compare + row-sum on the VPU (8x128 lanes) — no gather, no sort.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _bucket_kernel(h_ref, cum_ref, out_ref, *, total: int):
    h = h_ref[...].astype(jnp.int32)                       # (bt,)
    hm = jnp.mod(h, total)
    cum = cum_ref[...].astype(jnp.int32)                   # (E,)
    # bucket = number of inclusive prefix sums <= h
    out_ref[...] = jnp.sum(
        (cum[None, :] <= hm[:, None]).astype(jnp.int32), axis=1)


def skewed_bucket(hashes: jnp.ndarray, capacities: jnp.ndarray, *,
                  block: int = 1024, interpret: bool = False) -> jnp.ndarray:
    """hashes: (T,) int32; capacities: (E,) int32 (static shape).

    Returns (T,) int32 bucket ids in [0, E). The capacity *values* may be
    traced (HeMT re-skews them between steps without recompiling), but the
    hash-space size is their sum — we fold the mod into the kernel with the
    total passed as an operand to stay trace-safe.
    """
    t = hashes.shape[0]
    e = capacities.shape[0]
    tp = _round_up(t, block)
    if tp != t:
        hashes = jnp.pad(hashes, (0, tp - t))
    cum = jnp.cumsum(capacities.astype(jnp.int32))
    # a concrete vector (numpy, or a jax array closed over by a jit) folds
    # its total into the kernel; only a tracer needs the two-operand path
    total = (None if isinstance(capacities, jax.core.Tracer)
             else int(np.asarray(capacities).sum()))

    if total is None:
        # traced capacities: fall back to a two-operand kernel with the
        # total folded into the hashes outside (mod is cheap in XLA)
        hm = jnp.mod(hashes.astype(jnp.int32), cum[-1])
        kernel = functools.partial(_bucket_kernel, total=jnp.iinfo(jnp.int32).max)
        src = hm
    else:
        kernel = functools.partial(_bucket_kernel, total=total)
        src = hashes

    out = pl.pallas_call(
        kernel,
        grid=(tp // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((e,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((tp,), jnp.int32),
        interpret=interpret,
    )(src, cum)
    return out[:t]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
