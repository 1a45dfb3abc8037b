"""Plain float32 decoder, its loss and its training recipe.

The model (a pre-norm decoder with tied embeddings):
  x = E[tokens]
  per layer: x += Attn(rms(x) * n1);  x += FFN(rms(x) * n2)
  logits = (rms(x) * n_final) E^T     over the published vocabulary
Attention is causal GQA with rotary positions on interleaved pairs
(dims 2i, 2i+1 rotate by pos * theta**(-2i/head_dim)) and scale
head_dim**-0.5: the program's convention, a fixed permutation of HF's
rotate-half. The loss of a sequence is the mean next-token cross-entropy
plus the FFN's auxiliary loss summed over layers.

Training (the traffic file states the numbers): the loss and gradient are
the means over the step's sequences; the gradient is clipped to a global
norm; AdamW with decoupled weight decay on every leaf but those the
traffic file exempts; learning rate by linear warmup to the peak over
``warmup_steps`` (step ``s`` gets ``(s+1)/warmup`` of it), then a cosine
to a tenth of it at ``schedule_steps``. Parameters are stored after each
update in the type the configuration states for them (bfloat16 matrices:
held here as float32 rounded to bfloat16), moments in float32. Every product is float32 at full precision, unless
``lowp`` asks for the fp8 control.

Attention, the loss over the vocabulary and each layer are computed in
blocks under ``jax.checkpoint``, so that a whole row of 16k tokens fits
beside the optimizer state.
"""
from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights as W
from . import ffn_module
from .numerics import einsum, mm

LAYER_KEYS = ("norm1", "wq", "wk", "wv", "wo", "norm2",
              "router", "w_gate", "w_up", "w_down")
SCORE_BYTES = 1 << 28   # a block of attention scores, float32
LOGIT_BLOCK = 1024      # positions per block of the loss


def unstack(w: dict) -> dict:
    """Stacked weights -> {"embed", "final_norm", "layers": [dict, ...]}."""
    n = w["wq"].shape[0]
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "layers": [{k: w[k][i] for k in LAYER_KEYS if k in w}
                       for i in range(n)]}


def flat(p: dict) -> dict:
    out = {"embed": p["embed"], "final_norm": p["final_norm"]}
    for i, lp in enumerate(p["layers"]):
        out.update({f"{k}.{i}": v for k, v in lp.items()})
    return out


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, theta: float):
    """x (S, H, dh) float32, positions 0..S-1."""
    s, _, dh = x.shape
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv.astype(np.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(p: dict, x, c: dict, lowp: bool):
    """Causal GQA self-attention of one sequence x (S, D)."""
    s = x.shape[0]
    h, kvh = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    dh = int(c["assumed"]["head_dim"])
    theta = float(c["rope_theta"])
    q = rope(mm(x, p["wq"], lowp).reshape(s, h, dh), theta)
    k = rope(mm(x, p["wk"], lowp).reshape(s, kvh, dh), theta)
    v = mm(x, p["wv"], lowp).reshape(s, kvh, dh)
    g = h // kvh
    blk = max(b for b in range(1, s + 1)
              if s % b == 0 and (b == 1 or h * b * s * 4 <= SCORE_BYTES))
    qb = q.reshape(s // blk, blk, kvh, g, dh)

    def block(args):
        i, qi = args
        sc = einsum("qhgd,khd->hgqk", qi, k, lowp) / math.sqrt(dh)
        qpos = i * blk + jnp.arange(blk)
        ok = qpos[:, None] >= jnp.arange(s)[None, :]
        pr = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
        return einsum("hgqk,khd->qhgd", pr, v, lowp)

    out = jax.lax.map(jax.checkpoint(block), (jnp.arange(s // blk), qb))
    return mm(out.reshape(s, h * dh), p["wo"], lowp)


def hidden(p: dict, tokens, c: dict, lowp: bool):
    """Final-norm hidden states (S, D) of one sequence, and the aux loss."""
    eps = float(c["rms_norm_eps"])
    ffn = ffn_module(c).ffn

    def layer(x, lp):
        x = x + attention(lp, rms(x, lp["norm1"], eps), c, lowp)
        out, aux = ffn(lp, rms(x, lp["norm2"], eps), c, lowp)
        return x + out, aux

    x = p["embed"][tokens].astype(jnp.float32)
    aux = jnp.zeros((), jnp.float32)
    for lp in p["layers"]:
        x, a = jax.checkpoint(layer)(x, lp)
        aux = aux + a
    return rms(x, p["final_norm"], eps), aux


def logits(p: dict, hid, lowp: bool):
    return mm(hid, p["embed"].T, lowp)


def seq_loss(p: dict, tokens, labels, c: dict, lowp: bool):
    """Mean next-token cross-entropy of one sequence, plus the aux loss."""
    hid, aux = hidden(p, tokens, c, lowp)
    s = hid.shape[0]
    blk = min(LOGIT_BLOCK, s)

    def block(args):
        hb, lb = args
        lg = logits(p, hb, lowp)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, lb[:, None], -1)[:, 0])

    nll = jax.lax.map(jax.checkpoint(block),
                      (hid.reshape(s // blk, blk, -1), labels.reshape(s // blk, blk)))
    return jnp.sum(nll) / s + aux


def lr_at(step: int, t: dict) -> float:
    """The traffic file's warmup-cosine learning rate at 0-based ``step``."""
    peak, w, n = float(t["peak_lr"]), int(t["warmup_steps"]), int(t["schedule_steps"])
    if step < w:
        return peak * (step + 1) / max(w, 1)
    prog = min(max((step - w) / max(n - w, 1), 0.0), 1.0)
    return peak * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * prog)))


def _norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in flat(tree).items()}


def _stored(x, dtype):
    """x rounded to the type its parameter is stored in, kept as float32.

    ``reduce_precision`` rounds as a cast would, to nearest even, and XLA
    keeps it, where it may drop a cast there and back under its default
    excess-precision rule."""
    f = jnp.finfo(dtype)
    if f.bits == 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=f.nexp, mantissa_bits=f.nmant)


@partial(jax.jit, static_argnames=("c_json", "t_json", "lowp"),
         donate_argnums=(0, 1, 2))
def _step(p, mu, nu, tokens, labels, lr, count, *, c_json, t_json, lowp):
    """One training step. ``p`` holds each parameter as float32, already
    rounded to its stored type; the gradient of the summed loss is taken
    through a scan over the rows, so that each row's gradient is added to
    the sum as it is made."""
    c, t = json.loads(c_json), json.loads(t_json)
    stored = {k: dt for k, (_, dt, _) in W.shapes(c).items()}
    row_loss = jax.checkpoint(lambda q, tk, lb: seq_loss(q, tk, lb, c, lowp))

    def total(q):
        def body(acc, row):
            return acc + row_loss(q, row[0], row[1]), None
        return jax.lax.scan(body, jnp.zeros((), jnp.float32), (tokens, labels))[0]

    lsum, gsum = jax.value_and_grad(total)(p)
    n = tokens.shape[0]
    grads = jax.tree.map(lambda g: g / n, gsum)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = float(t["grad_clip"])
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2, eps = float(t["beta1"]), float(t["beta2"]), float(t["adam_eps"])
    wd = float(t["weight_decay"])
    no_decay = set(t["no_decay"])
    fm, fv, fg, fp = flat(mu), flat(nu), flat(grads), flat(p)
    new_p, new_m, new_v = {}, {}, {}
    for name in fp:
        base = name.split(".")[0]
        m = b1 * fm[name] + (1 - b1) * fg[name]
        v = b2 * fv[name] + (1 - b2) * fg[name] ** 2
        delta = (m / (1 - b1 ** count)) / (jnp.sqrt(v / (1 - b2 ** count)) + eps)
        if base not in no_decay:
            delta = delta + wd * fp[name]
        new_p[name] = _stored(fp[name] - lr * delta, stored[base])
        new_m[name], new_v[name] = m, v
    return (_unflat(new_p, p), _unflat(new_m, p), _unflat(new_v, p),
            lsum / n, _norms(grads))


def _unflat(f: dict, like: dict) -> dict:
    return {"embed": f["embed"], "final_norm": f["final_norm"],
            "layers": [{k: f[f"{k}.{i}"] for k in lp}
                       for i, lp in enumerate(like["layers"])]}


def train(make_weights, batches, c: dict, t: dict, *, lowp: bool = False) -> dict:
    """Follows the program's first ``len(batches)`` training steps.

    make_weights: () -> stacked weights as ``weights.make`` gives them;
    called at the start and again at the end for the change, so that no
    second copy is held through the steps. batches: per step, a pair of
    int32 arrays (tokens, labels) of shape (rows, seq).
    Returns per step the loss, the first step's clipped gradient norm per
    leaf, and the norm of each leaf's change over all the steps.
    """
    p = jax.tree.map(lambda a: a.astype(jnp.float32), unstack(make_weights()))
    mu = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p)
    nu = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p)
    c_json, t_json = json.dumps(c, sort_keys=True), json.dumps(t, sort_keys=True)
    losses, grad_norms = [], None
    for s, (tok, lab) in enumerate(batches):
        p, mu, nu, loss, gn = _step(p, mu, nu, jnp.asarray(tok), jnp.asarray(lab),
                                    jnp.float32(lr_at(s, t)), jnp.float32(s + 1),
                                    c_json=c_json, t_json=t_json, lowp=lowp)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {k: float(v) for k, v in gn.items()}
    del mu, nu
    change = _change(p, unstack(make_weights()))
    return {"loss": losses, "grad_norm": grad_norms,
            "change_norm": {k: float(v) for k, v in change.items()}}


@jax.jit
def _change(p, p0):
    return _norms(jax.tree.map(lambda a, b: a.astype(jnp.float32)
                               - b.astype(jnp.float32), p, p0))


@partial(jax.jit, static_argnames=("c_json", "lowp", "start"))
def _seq_logits(p, tokens, *, c_json, lowp, start):
    hid, _ = hidden(p, tokens, json.loads(c_json), lowp)
    return logits(p, hid[start:], lowp)


def serve_logits(weights: dict, tokens, start: int, c: dict, *, lowp: bool = False):
    """Logits (S - start, V) of one sequence at positions start..S-1."""
    return _seq_logits(unstack(weights), jnp.asarray(tokens),
                       c_json=json.dumps(c, sort_keys=True), lowp=lowp, start=start)


