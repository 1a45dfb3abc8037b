"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.skewed_hash import bucket_of, integer_capacities
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention as fa_kernel

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,sq,sk,d", [
    (1, 2, 2, 64, 64, 16),
    (2, 4, 2, 96, 96, 32),      # GQA + non-128 seq (padding path)
    (1, 8, 1, 128, 256, 64),    # MQA, cross lengths
    (1, 2, 2, 33, 65, 16),      # ragged padding
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 24), (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, hq, hkv, sq, sk, d, causal, window, dtype):
    if causal and sq != sk:
        pytest.skip("causal needs square")
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, sk, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, sk, d), dtype)
    out = fa_kernel(q, k, v, causal=causal, window=window,
                    block_q=32, block_k=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=1e-2)


def test_flash_ops_wrapper_model_layout():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 32))
    k = jax.random.normal(ks[1], (2, 64, 2, 32))
    v = jax.random.normal(ks[2], (2, 64, 2, 32))
    out = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                              interpret=True)
    want = jnp.swapaxes(ref.flash_attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=True), 1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# --------------------------------------------------------------------------
# SSD scan
# --------------------------------------------------------------------------

@pytest.mark.parametrize("bsz,s,h,p,g,n,chunk", [
    (1, 64, 2, 16, 1, 8, 16),
    (2, 96, 4, 8, 2, 16, 32),
    (1, 50, 4, 16, 4, 8, 16),    # padding path (50 % 16 != 0)
])
@pytest.mark.parametrize("with_init", [False, True])
def test_ssd_scan_sweep(bsz, s, h, p, g, n, chunk, with_init):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (bsz, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, h)))
    a_log = jnp.log(jnp.linspace(1.0, 8.0, h))
    B = jax.random.normal(ks[2], (bsz, s, g, n)) * 0.3
    C = jax.random.normal(ks[3], (bsz, s, g, n)) * 0.3
    init = (jax.random.normal(ks[4], (bsz, h, p, n)) * 0.1
            if with_init else None)
    y, f = ops.ssd_scan(x, dt, a_log, B, C, chunk=chunk, init_state=init,
                        interpret=True)
    yr, fr = ref.ssd_scan_ref(x, dt, a_log, B, C, init_state=init)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-3)
    np.testing.assert_allclose(np.asarray(f), np.asarray(fr), atol=2e-3)


# --------------------------------------------------------------------------
# skewed bucket (Algorithm 1)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("weights", [[1.0, 0.4], [1.0, 1.0, 1.0],
                                     [3, 4, 4], [0.5, 0.3, 0.1, 0.1]])
@pytest.mark.parametrize("t", [17, 1024, 5000])
def test_skewed_bucket_sweep(weights, t):
    caps = integer_capacities(weights, resolution=997)
    hashes = jax.random.randint(KEY, (t,), 0, 2**30)
    got = ops.skewed_bucket(hashes, jnp.asarray(caps, jnp.int32),
                            interpret=True)
    want_ref = ref.skewed_bucket_ref(hashes, jnp.asarray(caps, jnp.int32))
    want_np = bucket_of(np.asarray(hashes), caps)
    assert (np.asarray(got) == np.asarray(want_ref)).all()
    assert (np.asarray(got) == want_np).all()
    assert got.min() >= 0 and got.max() < len(weights)
