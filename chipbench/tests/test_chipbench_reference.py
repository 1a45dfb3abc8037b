"""The plain references against the program's models/ at toy widths on the
CPU: with float32 weights both compute the same function, so loss,
gradients and decode logits agree to float32 round-off."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights as W
from chipbench.program import Program
from chipbench.reference import decoder, moe
from chipbench.tests import tiny


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def setup(c, seq=32):
    prog = Program(c, "granite-3-8b" if "num_local_experts" not in c
                   else "granite-moe-1b-a400m")
    w = f32(W.make(c, W.seed_words(5)))
    toks = W.rows(5, W.TRAIN_STREAM, [0], seq + 1, int(c["vocab_size"]))[0]
    return prog, w, toks[:-1], toks[1:]


@pytest.mark.parametrize("make", [tiny.dense, tiny.moe], ids=["dense", "moe"])
def test_loss_and_gradients_match_models(make):
    from repro.models.model import loss_fn
    c = make()
    prog, w, tok, lab = setup(c)
    batch = {"tokens": jnp.asarray(tok)[None], "labels": jnp.asarray(lab)[None]}

    def prog_loss(w):
        return loss_fn(prog.tree(w), batch, prog.cfg)

    def ref_loss(w):
        return decoder.seq_loss(decoder.unstack(w), jnp.asarray(tok),
                                jnp.asarray(lab), c, False)

    lp, gp = jax.value_and_grad(prog_loss)(w)
    lr, gr = jax.value_and_grad(ref_loss)(w)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for k in gr:
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gr[k]),
                                   rtol=2e-3, atol=1e-6, err_msg=k)


def test_moe_reference_drops_past_capacity():
    c = tiny.moe()
    _, w, tok, _ = setup(c)
    p = decoder.unstack(w)["layers"][0]
    x = jnp.asarray(np.random.default_rng(0).normal(size=(32, 64)), jnp.float32)
    cap = moe.capacity(32, c)
    assert cap == 10   # ceil(ceil(32 * 2 * 1.25) / 8)
    from repro.models.moe import moe_apply
    from chipbench.program import model_config
    cfg = model_config(c, "granite-moe-1b-a400m")
    want, aux_p = moe_apply({k: v[0] for k, v in prog_moe(w).items()}, x[None], cfg.moe)
    got, aux_r = moe.ffn(p, x, c, False)
    np.testing.assert_allclose(np.asarray(want[0]), np.asarray(got), rtol=1e-4, atol=1e-5)
    assert float(aux_p) == pytest.approx(float(aux_r), rel=1e-5)
    # with a tenth of the capacity many choices drop, and the two still agree
    c2 = dict(c, assumed=dict(c["assumed"], capacity_factor=0.125))
    cfg2 = model_config(c2, "granite-moe-1b-a400m")
    want2, _ = moe_apply({k: v[0] for k, v in prog_moe(w).items()}, x[None], cfg2.moe)
    got2, _ = moe.ffn(p, x, c2, False)
    np.testing.assert_allclose(np.asarray(want2[0]), np.asarray(got2), rtol=1e-4, atol=1e-5)
    assert not np.allclose(np.asarray(got2), np.asarray(got))


def prog_moe(w):
    return {k: w[k] for k in ("router", "w_gate", "w_up", "w_down")}


def test_decode_through_the_cache_matches_reference_forward():
    from repro.runtime.serve_loop import make_prefill_step, make_serve_step
    c = tiny.dense()
    prog, w, tok, _ = setup(c)
    params = prog.tree(w)
    prompt = jnp.asarray(tok[:16])[None]
    nxt, st = jax.jit(make_prefill_step(prog.cfg, 24))(params, prompt)
    step = jax.jit(make_serve_step(prog.cfg))
    served, logits = [nxt], []
    for _ in range(8):
        nxt, lg, st = step(params, st, nxt)
        served.append(nxt)
        logits.append(lg[0, :prog.vocab])
    seq = np.concatenate([tok[:16], np.asarray(jnp.stack(served[:8]))[:, 0]])
    ref = np.asarray(decoder.serve_logits(w, seq, 16, c))
    np.testing.assert_allclose(np.asarray(jnp.stack(logits)), ref, rtol=1e-4, atol=1e-4)
