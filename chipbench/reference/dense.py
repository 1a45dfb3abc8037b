"""Dense SwiGLU feed-forward block: silu(x Wg) * (x Wu), then Wd."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .numerics import mm


def ffn(p: dict, x, config: dict, lowp: bool):
    """x (S, D) float32 -> (out (S, D), aux loss 0)."""
    h = jax.nn.silu(mm(x, p["w_gate"], lowp)) * mm(x, p["w_up"], lowp)
    return mm(h, p["w_down"], lowp), jnp.zeros((), jnp.float32)
