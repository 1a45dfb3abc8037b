"""Per-arch reduced smoke tests + model math invariants.

Every assigned architecture: instantiate the REDUCED config, run one
forward + one train step on CPU, assert output shapes and no NaNs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_bundle, get_reduced
from repro.configs.base import MoEConfig, padded_vocab_size
from repro.models import forward, init_params, loss_fn
from repro.models.attention import (
    chunked_attention, dot_product_attention, _mask_bias,
)
from repro.models.frontends import stub_feature_shape
from repro.models.model import decode_step, init_decode_state, prefill
from repro.runtime.train_loop import make_train_step, train_state_init

KEY = jax.random.PRNGKey(0)
B, S = 2, 24


def _batch_for(cfg):
    batch = {"labels": jnp.zeros((B, S), jnp.int32)}
    if cfg.frontend == "vision":
        batch["input_embeds"] = jnp.ones(stub_feature_shape(cfg, B, S),
                                         jnp.float32) * 0.02
    else:
        batch["tokens"] = jax.random.randint(KEY, (B, S), 1, cfg.vocab_size)
    if cfg.encoder_layers > 0:
        batch["enc_feats"] = jnp.ones(stub_feature_shape(cfg, B, 16),
                                      jnp.float32) * 0.05
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward_and_train_step(arch):
    cfg = get_reduced(arch)
    bundle = get_bundle(arch).replace(model=cfg)
    params = init_params(KEY, cfg)
    batch = _batch_for(cfg)

    logits, aux = forward(params, batch.get("tokens"), cfg,
                          input_embeds=batch.get("input_embeds"),
                          enc_feats=batch.get("enc_feats"))
    assert logits.shape == (B, S, padded_vocab_size(cfg))
    assert np.isfinite(np.asarray(logits, np.float32)).all()

    state = train_state_init(KEY, cfg, bundle)
    step = make_train_step(cfg, bundle)
    state2, metrics = jax.jit(step)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(state2.step) == 1
    # params actually moved
    delta = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))),
        state.params, state2.params)
    assert max(jax.tree.leaves(delta)) > 0


@pytest.mark.parametrize("arch", ["granite-3-8b", "gemma3-12b",
                                  "mamba2-2.7b", "jamba-1.5-large-398b",
                                  "whisper-medium"])
def test_prefill_matches_stepwise_decode(arch):
    cfg = dataclasses.replace(get_reduced(arch), dtype="float32")
    if cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.n_experts)))  # no drops
    params = init_params(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, 10), 1,
                              cfg.vocab_size)
    kw = {}
    enc_out = None
    if cfg.encoder_layers > 0:
        kw["enc_feats"] = jnp.ones(stub_feature_shape(cfg, B, 16),
                                   jnp.float32) * 0.1
        from repro.models.model import encode
        enc_out = encode(params, kw["enc_feats"], cfg)
    logits_pf, state_pf = prefill(params, toks, cfg, 32, **kw)
    state = init_decode_state(cfg, B, 32)
    for t in range(10):
        logits_dec, state = decode_step(params, state, toks[:, t], cfg,
                                        enc_out=enc_out)
    np.testing.assert_allclose(np.asarray(logits_pf), np.asarray(logits_dec),
                               atol=5e-4)
    cache_err = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        state_pf["cache"], state["cache"])
    assert max(jax.tree.leaves(cache_err)) < 5e-4


# --------------------------------------------------------------------------
# plain oracle of the decode step: the stacked cache as the layer scan's
# xs/ys, each layer's K/V sequence-major (B, S, Hkv, Dh)
# --------------------------------------------------------------------------

def _oracle_attention_decode_step(params, x, cache, cache_len, cfg, *,
                                  window_override=None, kv_source=None):
    from repro.models.layers import apply_rope
    b = x.shape[0]
    dh, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    cap = cache["k"].shape[1]
    scale = cfg.scale if cfg.scale is not None else 1.0 / np.sqrt(dh)
    q = (x @ params["wq"]).reshape(b, 1, hq, dh)
    if kv_source is not None:
        sk = kv_source.shape[1]
        k = (kv_source @ params["wk"]).reshape(b, sk, hkv, dh)
        v = (kv_source @ params["wv"]).reshape(b, sk, hkv, dh)
        out = dot_product_attention(q, k, v, None, scale)
        return out.reshape(b, 1, hq * dh) @ params["wo"], cache
    pos = jnp.full((b, 1), cache_len, jnp.int32)
    q = apply_rope(q, pos, cfg.rope_theta, cfg.rope_style)
    k_new = (x @ params["wk"]).reshape(b, 1, hkv, dh)
    k_new = apply_rope(k_new, pos, cfg.rope_theta, cfg.rope_style)
    v_new = (x @ params["wv"]).reshape(b, 1, hkv, dh)
    slot = jnp.mod(cache_len, cap)
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1)
    idx = jnp.arange(cap)
    abs_pos = cache_len - jnp.mod(cache_len - idx, cap)
    valid = abs_pos >= 0
    window = cfg.sliding_window if window_override is None else window_override
    if window > 0:
        valid &= (cache_len - abs_pos) < window
    bias = jnp.where(valid, 0.0, -1e30)[None, None, :]
    out = dot_product_attention(q, k_cache, v_cache,
                                jnp.broadcast_to(bias, (b, 1, cap)), scale)
    out = out.reshape(b, 1, hq * dh) @ params["wo"]
    return out, {"k": k_cache, "v": v_cache}


def _oracle_stack_decode_step(params, cache, x, cache_len, cfg, enc_out):
    from repro.models import moe as moe_mod, ssm as ssm_mod
    from repro.models.layers import mlp_apply, rmsnorm

    def group_body(h, scanned):
        gparams, gcache = scanned
        new_gcache = {}
        for j in range(cfg.layer_period):
            p, c = gparams[f"sub{j}"], gcache[f"sub{j}"]
            hin = rmsnorm(p["norm1"], h, cfg.norm_eps)
            if cfg.layer_kind(j) == "attn":
                acfg = cfg.attention
                window = None
                if acfg.local_global != (0, 0):
                    window = 0 if cfg.layer_is_global_attn(j) else acfg.sliding_window
                out, c = _oracle_attention_decode_step(
                    p["mixer"], hin, c, cache_len, acfg, window_override=window)
            else:
                out, c = ssm_mod.ssm_decode_step(p["mixer"], hin, c,
                                                 cfg.d_model, cfg.ssm)
            h = h + out
            if "cross" in p:
                hin = rmsnorm(p["norm_cross"], h, cfg.norm_eps)
                out, _ = _oracle_attention_decode_step(
                    p["cross"], hin, c, cache_len, cfg.attention,
                    kv_source=enc_out)
                h = h + out
            if "ffn" in p:
                hin = rmsnorm(p["norm2"], h, cfg.norm_eps)
                if cfg.layer_is_moe(j):
                    out, _ = moe_mod.moe_apply(p["ffn"], hin, cfg.moe, cfg.act)
                else:
                    out = mlp_apply(p["ffn"], hin, cfg.act)
                h = h + out
            new_gcache[f"sub{j}"] = c
        return h, new_gcache

    return jax.lax.scan(group_body, x, (params, cache))


def _oracle_decode_step(params, state, token, cfg, enc_out):
    from repro.models.layers import embed, rmsnorm, unembed
    from repro.models.model import _sin_row, mask_pad_logits
    x = embed(params["embed"], token[:, None])
    if cfg.attention is not None and cfg.attention.rope_style == "none" \
            and cfg.encoder_layers > 0:
        x = x + _sin_row(state["length"], cfg.d_model).astype(x.dtype)[None, None]
    x, cache = _oracle_stack_decode_step(params["stack"], state["cache"], x,
                                         state["length"], cfg, enc_out)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = params["unembed"] if "unembed" in params else params["embed"]
    logits = mask_pad_logits(unembed(head, x)[:, 0, :], cfg)
    return logits, {"cache": cache, "length": state["length"] + 1}


def _sequence_major(cache):
    """The head-major attention cache (L, B, Hkv, S, Dh) in the oracle's
    (L, B, S, Hkv, Dh); SSM leaves keep their layout."""
    return jax.tree_util.tree_map_with_path(
        lambda path, t: t.transpose(0, 1, 3, 2, 4)
        if path[-1].key in ("k", "v") else t, cache)


@pytest.mark.parametrize("arch,max_len,steps", [
    ("gemma3-12b", 32, 34),          # 6 + 34 = 40 positions: both rings wrap
    ("granite-3-8b", 16, 8),
    ("mamba2-2.7b", 16, 8),
    ("jamba-1.5-large-398b", 16, 8),
    ("whisper-medium", 16, 8)])
def test_decode_step_matches_sequence_major_oracle(arch, max_len, steps):
    """The in-place decode step gives the oracle's logits and cache, through
    a prefill of 6 tokens and ``steps`` decode steps past it."""
    cfg = dataclasses.replace(get_reduced(arch), dtype="float32")
    if cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.n_experts)))  # no drops
    params = init_params(jax.random.PRNGKey(3), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, 6 + steps), 1,
                              cfg.vocab_size)
    kw, enc_out = {}, None
    if cfg.encoder_layers > 0:
        kw["enc_feats"] = jnp.ones(stub_feature_shape(cfg, B, 16),
                                   jnp.float32) * 0.1
        from repro.models.model import encode
        enc_out = encode(params, kw["enc_feats"], cfg)
    _, state = prefill(params, toks[:, :6], cfg, max_len, **kw)
    ref = {"cache": _sequence_major(state["cache"]), "length": state["length"]}
    step = jax.jit(lambda s, t: decode_step(params, s, t, cfg, enc_out=enc_out))
    ref_step = jax.jit(lambda s, t: _oracle_decode_step(params, s, t, cfg,
                                                        enc_out))
    for t in range(6, 6 + steps):
        logits, state = step(state, toks[:, t])
        ref_logits, ref = ref_step(ref, toks[:, t])
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                                   rtol=1e-5, atol=1e-5)
    got = _sequence_major(state["cache"])
    assert jax.tree.structure(got) == jax.tree.structure(ref["cache"])
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref["cache"])):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def _oracle_chunked_attention(q, k, v, *, causal, window, scale, block_q,
                              block_k):
    """The streaming attention as it was before tiles were skipped: every
    query block scans every key block and the mask zeroes the closed ones."""
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bq, bk = min(block_q, sq), min(block_k, sk)
    sq_p, sk_p = -(-sq // bq) * bq, -(-sk // bk) * bk
    q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    nq, nk = sq_p // bq, sk_p // bk
    qb = q.reshape(b, nq, bq, hkv, g, d).transpose(1, 0, 3, 4, 2, 5)
    kb = k.reshape(b, nk, bk, hkv, d).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(b, nk, bk, hkv, d).transpose(1, 0, 3, 2, 4)

    def q_block(args):
        qi, qt = args

        def kv_step(carry, inp):
            m_p, l_p, acc = carry
            ki, kt, vt = inp
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qt.astype(jnp.float32),
                           kt.astype(jnp.float32)) * scale
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            ok = kpos < sk
            if causal:
                ok &= qpos - kpos >= 0
            if window > 0:
                ok &= qpos - kpos < window
            s = jnp.where(ok[None, None, None], s, -1e30)
            m_n = jnp.maximum(m_p, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_n)
            alpha = jnp.exp(m_p - m_n)
            l_n = l_p * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum("bhgqk,bhkd->bhgqd", p,
                                           vt.astype(jnp.float32))
            return (m_n, l_n, acc), None

        kv_step = jax.checkpoint(
            kv_step, policy=jax.checkpoint_policies.nothing_saveable)
        init = (jnp.full((b, hkv, g, bq, 1), -1e30, jnp.float32),
                jnp.zeros((b, hkv, g, bq, 1), jnp.float32),
                jnp.zeros((b, hkv, g, bq, d), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, init, (jnp.arange(nk), kb, vb))
        return acc / jnp.where(l == 0.0, 1.0, l)

    q_block = jax.checkpoint(
        q_block, policy=jax.checkpoint_policies.nothing_saveable)
    out = jax.lax.map(q_block, (jnp.arange(nq), qb))
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq_p, hq, d)
    return out[:, :sq].astype(q.dtype)


# (sq, causal, window, block_q, block_k)
CHUNKED_CASES = {
    "causal-bk<bq": (80, True, 0, 32, 16),
    "causal-bk>bq": (64, True, 0, 16, 32),
    "window<block": (80, True, 5, 32, 16),
    "window17": (80, True, 17, 32, 16),
    "window-spans-blocks": (80, True, 40, 16, 32),
    "noncausal": (80, False, 0, 32, 16),
    "noncausal-window": (80, False, 9, 16, 32),
    "sq-not-a-multiple": (72, True, 0, 32, 16),
}


def _chunked_case(case, batch, hq, hkv):
    sq, causal, win, bq, bk = CHUNKED_CASES[case]
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (batch, sq, hq, 16))
    k = jax.random.normal(ks[1], (batch, sq, hkv, 16))
    v = jax.random.normal(ks[2], (batch, sq, hkv, 16))
    pos = jnp.broadcast_to(jnp.arange(sq)[None], (batch, sq))
    kw = dict(causal=causal, window=win, scale=0.25, block_q=bq, block_k=bk)

    def dense(q, k, v):
        return dot_product_attention(q, k, v, _mask_bias(pos, pos, causal, win),
                                     0.25)
    return (q, k, v), kw, dense


@pytest.mark.parametrize("case", list(CHUNKED_CASES))
def test_chunked_attention_equals_dense(case):
    """The tile-skipping streaming attention gives what visiting every tile
    gave, and what the dense masked softmax gives."""
    (q, k, v), kw, dense = _chunked_case(case, 2, 4, 2)
    got = jax.jit(lambda q, k, v: chunked_attention(q, k, v, **kw))(q, k, v)
    want = jax.jit(lambda q, k, v: _oracle_chunked_attention(q, k, v, **kw))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want(q, k, v)),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense(q, k, v)),
                               atol=2e-5)


@pytest.mark.parametrize("case", list(CHUNKED_CASES))
def test_chunked_attention_gradients_match(case):
    """Gradients through the skipped tiles' schedule: against the oracle
    that visits every tile, and against the dense masked softmax."""
    (q, k, v), kw, dense = _chunked_case(case, 1, 2, 2)

    def loss(f):
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) ** 2),
                                (0, 1, 2)))

    g = loss(lambda q, k, v: chunked_attention(q, k, v, **kw))(q, k, v)
    g_oracle = loss(lambda q, k, v: _oracle_chunked_attention(q, k, v, **kw))(
        q, k, v)
    g_dense = loss(dense)(q, k, v)
    for a, b, c in zip(g, g_oracle, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=3e-4)


def test_moe_capacity_skew_shifts_tokens():
    """HeMT-EP: skewed shard capacities change per-expert slot budgets."""
    from repro.models.moe import expert_capacities
    cfg = MoEConfig(n_experts=4, top_k=2)
    even = expert_capacities(cfg, tokens_per_group=64)
    assert len(set(even.tolist())) == 1
    skew_cfg = MoEConfig(n_experts=4, top_k=2,
                         shard_capacities=(1.0, 1.0, 1.0, 0.4))
    skew = expert_capacities(skew_cfg, tokens_per_group=64)
    assert skew.sum() == even.sum()      # fixed total buffer
    assert skew[3] < skew[0]             # slow shard gets fewer slots
    ratio = skew[3] / skew[0]
    assert abs(ratio - 0.4) < 0.15


def test_moe_sort_dispatch_matches_dense_oracle():
    from repro.models.moe import moe_apply, moe_apply_dense_fallback, moe_init
    cfg = MoEConfig(n_experts=4, top_k=2, capacity_factor=4.0)
    p = moe_init(KEY, 32, 64, cfg, glu=True, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 32))
    o1, a1 = moe_apply(p, x, cfg)
    o2, a2 = moe_apply_dense_fallback(p, x, cfg)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)
    assert float(a1) == pytest.approx(float(a2))


@pytest.mark.parametrize("batch", [1, 3], ids=["grouped", "capacity"])
@pytest.mark.parametrize("skew", [0.0, 6.0], ids=["even", "skewed"])
def test_moe_grouped_path_matches_dense_oracle(skew, batch):
    """Where every expert can take every token, one sequence takes the
    grouped dispatch and several the capacity path, and both give the dense
    oracle's output, aux loss and gradients; ``skew`` makes expert 0 take
    nearly every token."""
    from repro.models.moe import (
        is_dropless, moe_apply_dense_fallback, moe_init, moe_layer,
        takes_grouped_path,
    )
    cfg = MoEConfig(n_experts=4, top_k=2, capacity_factor=2.0)
    assert is_dropless(cfg, 16)
    assert takes_grouped_path(cfg, batch, 16) == (batch == 1)
    p = moe_init(KEY, 32, 64, cfg, glu=True, dtype=jnp.float32)
    p["router"] = p["router"].at[:, 0].add(skew / 32)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, 16, 32)) + 1.0
    ct = jax.random.normal(jax.random.PRNGKey(2), (batch, 16, 32))

    def grouped(p, x):
        out, aux, stats = moe_layer(p, x, cfg)
        return jnp.sum(out * ct) + aux, (out, stats)

    def oracle(p, x):
        out, aux = moe_apply_dense_fallback(p, x, cfg)
        return jnp.sum(out * ct) + aux, out

    (l1, (o1, stats)), g1 = jax.value_and_grad(grouped, (0, 1), has_aux=True)(p, x)
    (l2, o2), g2 = jax.value_and_grad(oracle, (0, 1), has_aux=True)(p, x)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)
    assert float(l1) == pytest.approx(float(l2), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)
    assert int(stats.routed_rows) == batch * 16 * 2 and int(stats.dropped_rows) == 0
    if skew:   # expert 0, nearly every token
        assert int(stats.max_expert_rows) >= 0.9 * batch * 16


def test_moe_capacity_just_below_s_takes_the_capacity_path_and_drops():
    from repro.models.moe import (
        expert_capacities, is_dropless, moe_apply_dense_fallback, moe_init, moe_layer,
    )
    cfg = MoEConfig(n_experts=4, top_k=2, capacity_factor=1.8)
    assert expert_capacities(cfg, 16).tolist() == [15] * 4 and not is_dropless(cfg, 16)
    p = moe_init(KEY, 32, 64, cfg, glu=True, dtype=jnp.float32)
    p["router"] = p["router"].at[:, 0].add(16.0 / 32)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 32)) + 1.0
    out, _, stats = moe_layer(p, x, cfg)
    # expert 0 is every token's choice: one row past its 15 slots a sequence
    assert int(stats.dropped_rows) == 3 and int(stats.routed_rows) == 96
    assert int(stats.max_expert_rows) == 3 * 16
    want, _ = moe_apply_dense_fallback(p, x, cfg)
    assert not np.allclose(np.asarray(out), np.asarray(want), atol=1e-4)


def test_pad_vocab_loss_exactness():
    """Pad-vocab logits must not leak probability mass into the loss."""
    arch = "granite-3-8b"          # 49155 -> padded 49408
    cfg = dataclasses.replace(get_reduced(arch), vocab_size=49155 % 997 + 130)
    assert padded_vocab_size(cfg) != cfg.vocab_size
    params = init_params(KEY, cfg)
    batch = {"tokens": jax.random.randint(KEY, (B, S), 1, cfg.vocab_size),
             "labels": jax.random.randint(KEY, (B, S), 1, cfg.vocab_size)}
    loss = loss_fn(params, batch, cfg)
    logits, _ = forward(params, batch["tokens"], cfg)
    # manual loss over the TRUE vocab slice only
    lg = np.asarray(logits, np.float32)[..., :cfg.vocab_size]
    lp = lg - np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1,
                     keepdims=True)) - lg.max(-1, keepdims=True)
    nll = -np.take_along_axis(lp, np.asarray(batch["labels"])[..., None],
                              -1).mean()
    assert float(loss) == pytest.approx(nll, rel=1e-3)


def test_rope_styles():
    from repro.models.layers import apply_rope
    x = jax.random.normal(KEY, (1, 8, 2, 16))
    pos = jnp.arange(8)[None]
    full = apply_rope(x, pos, 10_000.0, "full")
    half = apply_rope(x, pos, 10_000.0, "half")
    none = apply_rope(x, pos, 10_000.0, "none")
    assert (np.asarray(none) == np.asarray(x)).all()
    # half-style passes the second half of head dims through untouched
    np.testing.assert_array_equal(np.asarray(half[..., 8:]),
                                  np.asarray(x[..., 8:]))
    assert not np.allclose(np.asarray(full[..., 8:]), np.asarray(x[..., 8:]))
    # norm preserved (rotations)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(full), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)


@pytest.mark.parametrize("arch,kinds", [
    ("jamba-1.5-large-398b", ["ssm"] * 4 + ["attn"] + ["ssm"] * 3),
    ("mamba2-2.7b", ["ssm"] * 4),
    ("granite-3-8b", ["attn"] * 4),
])
def test_layer_kind_patterns(arch, kinds):
    cfg = get_reduced(arch)
    got = [cfg.layer_kind(i) for i in range(len(kinds))]
    assert got == kinds


def test_gemma3_local_global_pattern():
    cfg = get_reduced("gemma3-12b")
    pattern = [cfg.layer_is_global_attn(i) for i in range(6)]
    assert pattern == [False] * 5 + [True]


def test_chunked_xent_matches_dense():
    """Memory-lean vocab-chunked cross-entropy == dense loss, value + grad."""
    from repro.models.layers import unembed
    from repro.models.model import (
        _dense_xent, chunked_softmax_xent, hidden_states, mask_pad_logits,
    )

    cfg = dataclasses.replace(get_reduced("granite-3-8b"), vocab_size=1234,
                              dtype="float32")
    prm = init_params(KEY, cfg)
    batch = {"tokens": jax.random.randint(KEY, (2, 12), 1, cfg.vocab_size),
             "labels": jax.random.randint(KEY, (2, 12), 1, cfg.vocab_size)}

    def f_dense(p):
        x, aux, _ = hidden_states(p, batch["tokens"], cfg)
        logits = mask_pad_logits(unembed(p["embed"], x), cfg)
        return jnp.mean(_dense_xent(logits, batch["labels"])) + aux

    def f_chunk(p):
        x, aux, _ = hidden_states(p, batch["tokens"], cfg)
        nll = chunked_softmax_xent(x, p["embed"]["table"], batch["labels"],
                                   cfg.vocab_size, chunk=256)
        return jnp.mean(nll) + aux

    assert float(f_dense(prm)) == pytest.approx(float(f_chunk(prm)), abs=1e-4)
    g1, g2 = jax.grad(f_dense)(prm), jax.grad(f_chunk)(prm)
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), g1, g2)))
    assert err < 1e-4
