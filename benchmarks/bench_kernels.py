"""Kernel micro-benchmarks (interpret-mode correctness + XLA-path timing).

The Pallas kernels target TPU; on this CPU container we time the *XLA
twin* of each kernel (chunked attention / SSD scan / Algorithm 1 bucket
map) and allclose-check the Pallas interpret path, so the numbers are a
functional sanity record, not TPU performance."""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from benchmarks.common import BenchRow, timed
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def rows() -> List[BenchRow]:
    out = []
    # attention: XLA chunked path timing + pallas-vs-ref error
    from repro.models.attention import chunked_attention
    q = jax.random.normal(KEY, (1, 512, 8, 64))
    k = jax.random.normal(KEY, (1, 512, 2, 64))
    v = jax.random.normal(KEY, (1, 512, 2, 64))
    f = jax.jit(lambda q, k, v: chunked_attention(
        q, k, v, causal=True, window=0, scale=0.125))
    f(q, k, v)  # warm
    _, us = timed(lambda: jax.block_until_ready(f(q, k, v)))
    small = [x[:, :64] for x in (q, k, v)]
    pall = ops.flash_attention(*small, causal=True, block_q=32, block_k=32,
                               interpret=True)
    want = jnp.swapaxes(ref.flash_attention_ref(
        *(jnp.swapaxes(x, 1, 2) for x in small), causal=True), 1, 2)
    err = float(jnp.max(jnp.abs(pall - want)))
    out.append(BenchRow("kernel/attention_512", us,
                        f"pallas_interpret_maxerr={err:.1e}"))

    # ssd scan
    x = jax.random.normal(KEY, (1, 512, 8, 32)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(KEY, (1, 512, 8)))
    a_log = jnp.log(jnp.linspace(1., 8., 8))
    B = jax.random.normal(KEY, (1, 512, 2, 16)) * 0.3
    C = jax.random.normal(KEY, (1, 512, 2, 16)) * 0.3
    from repro.models.ssm import ssd_chunked
    g = jax.jit(lambda *a: ssd_chunked(*a, chunk=64))
    g(x, dt, a_log, B, C)
    _, us = timed(lambda: jax.block_until_ready(g(x, dt, a_log, B, C)[0]))
    y_p, f_p = ops.ssd_scan(x[:, :64], dt[:, :64], a_log, B[:, :64],
                            C[:, :64], chunk=32, interpret=True)
    y_r, f_r = ref.ssd_scan_ref(x[:, :64], dt[:, :64], a_log, B[:, :64],
                                C[:, :64])
    err = float(jnp.max(jnp.abs(y_p - y_r)))
    out.append(BenchRow("kernel/ssd_512", us,
                        f"pallas_interpret_maxerr={err:.1e}"))

    # Algorithm 1 bucket map
    caps = jnp.asarray([715, 285], jnp.int32)      # 1.0 : 0.4
    hashes = jax.random.randint(KEY, (1 << 16,), 0, 1 << 30)
    bk = ops.skewed_bucket(hashes, caps, interpret=True)
    br = ref.skewed_bucket_ref(hashes, caps)
    h = jax.jit(ref.skewed_bucket_ref)
    h(hashes, caps)
    _, us = timed(lambda: jax.block_until_ready(h(hashes, caps)))
    out.append(BenchRow("kernel/skewed_bucket_64k", us,
                        f"pallas_match={bool((bk == br).all())}"))
    return out


def main() -> None:
    from benchmarks.common import print_rows
    print_rows(rows())


if __name__ == "__main__":
    main()
