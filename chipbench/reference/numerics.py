"""Matrix products of the references: float32 at full precision, or, for
the control, with both operands rounded to fp8's precision first, the
precision below the bfloat16 that the configurations state. The rounding
keeps e4m3's 3 mantissa bits and float32's exponent range: fp8 with an
ideal per-tensor scale, so that small gradients are rounded, not flushed
to zero. The rounding is linear under autodiff, so the backward's
products are rounded alike."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def rnd(x, lowp: bool):
    x = x.astype(jnp.float32)
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3) \
        if lowp else x


def mm(a, b, lowp: bool):
    return jnp.matmul(rnd(a, lowp), rnd(b, lowp), precision=HIGHEST)


def einsum(spec, a, b, lowp: bool):
    return jnp.einsum(spec, rnd(a, lowp), rnd(b, lowp), precision=HIGHEST)
