"""Weights and token rows made from ``--seed``, independent of the program.

The weights are one flat dict of stacked layers (leading axis = layer),
made on the device in one jitted call, in the types they are served in:
bfloat16 matrices and embedding, float32 norm scales and router. The
program's parameter tree is built from this dict by ``program.py``; the
reference reads the dict as it is. Tokens are drawn uniformly from the
vocabulary on the host, one numpy generator per (seed, stream, index), so
any row can be made again without making the ones before it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dims(c: dict) -> dict:
    """The sizes the weights and the counters need, from a config file."""
    d = int(c["hidden_size"])
    h = int(c["num_attention_heads"])
    return {
        "layers": int(c["num_hidden_layers"]), "d": d,
        "ff": int(c["intermediate_size"]), "vocab": int(c["vocab_size"]),
        "heads": h, "kv_heads": int(c["num_key_value_heads"]),
        "head_dim": int(c.get("assumed", {}).get("head_dim", d // h)),
        "experts": int(c.get("num_local_experts", 0)),
        "top_k": int(c.get("num_experts_per_tok", 0)),
    }


def shapes(c: dict) -> dict:
    """name -> (shape, dtype, init scale); scale None means ones."""
    m = dims(c)
    L, d, ff, hd = m["layers"], m["d"], m["ff"], m["head_dim"]
    q, kv = m["heads"] * hd, m["kv_heads"] * hd
    bf, f32 = jnp.bfloat16, jnp.float32
    out = {
        "embed": ((m["vocab"], d), bf, d ** -0.5),
        "norm1": ((L, d), f32, None),
        "wq": ((L, d, q), bf, d ** -0.5),
        "wk": ((L, d, kv), bf, d ** -0.5),
        "wv": ((L, d, kv), bf, d ** -0.5),
        "wo": ((L, q, d), bf, q ** -0.5),
        "norm2": ((L, d), f32, None),
        "final_norm": ((d,), f32, None),
    }
    if m["experts"]:
        e = m["experts"]
        out.update({
            "router": ((L, d, e), f32, d ** -0.5),
            "w_gate": ((L, e, d, ff), bf, d ** -0.5),
            "w_up": ((L, e, d, ff), bf, d ** -0.5),
            "w_down": ((L, e, ff, d), bf, ff ** -0.5),
        })
    else:
        out.update({
            "w_gate": ((L, d, ff), bf, d ** -0.5),
            "w_up": ((L, d, ff), bf, d ** -0.5),
            "w_down": ((L, ff, d), bf, ff ** -0.5),
        })
    return out


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words, so that seeds past
    2**32 stay distinct and no seed is a static argument of a jitted call."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return np.asarray([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def key_of(words) -> jax.Array:
    key = jax.random.fold_in(jax.random.PRNGKey(0), words[0])
    return jax.random.fold_in(key, words[1])


def make(c: dict, words) -> dict:
    """Traceable: the weights of config ``c`` from the seed's two words."""
    key = key_of(words)
    out = {}
    for i, (name, (shape, dtype, scale)) in enumerate(sorted(shapes(c).items())):
        if scale is None:
            out[name] = jnp.ones(shape, dtype)
        else:
            k = jax.random.fold_in(key, i)
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * scale).astype(dtype)
    return out


def rows(seed: int, stream: int, indices, length: int, vocab: int) -> np.ndarray:
    """Token rows [len(indices), length] int32, row i a function of
    (seed, stream, indices[i]) alone."""
    out = np.empty((len(indices), length), np.int32)
    for j, i in enumerate(indices):
        rng = np.random.default_rng([seed, stream, int(i)])
        out[j] = rng.integers(0, vocab, size=length, dtype=np.int32)
    return out


TRAIN_STREAM, PROMPT_STREAM, SAMPLE_STREAM = 1, 2, 3

