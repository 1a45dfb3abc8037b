"""Compile-only tests for a described TPU v5e.

Nothing runs: each test lowers a kernel or step at real widths for one chip
of a described ``v5e:2x2`` topology and reads the compiled program. This is
what catches a block layout that Mosaic refuses or a step that does not fit
the chip's 16 GiB, which the CPU interpret-mode tests never see.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models.model import init_decode_state, init_params
from repro.runtime.serve_loop import make_serve_step

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: entries compiled for a chip that is not attached cannot be
    read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no libtpu here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def _kernel_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles_at_granite_widths(one_chip):
    q = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 128), jnp.bfloat16)
    hlo = _kernel_hlo(lambda q, k, v: ops.flash_attention(q, k, v),
                      *_on(one_chip, (q, kv, kv)))
    assert "tpu_custom_call" in hlo


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    # mamba2-2.7b: d_inner 5120 = 80 heads x 64, state 128, one group
    s = 4096
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((1, s, 80, 64), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, s, 80), jnp.float32),
        jax.ShapeDtypeStruct((80,), jnp.float32),
        jax.ShapeDtypeStruct((1, s, 1, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, s, 1, 128), jnp.bfloat16)))
    hlo = _kernel_hlo(
        lambda x, dt, a, B, C: ops.ssd_scan(x, dt, a, B, C, chunk=256), *args)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("capacities", ["traced", "jnp", "numpy"])
def test_skewed_bucket_compiles(one_chip, capacities):
    hashes = _on(one_chip, jax.ShapeDtypeStruct((65536,), jnp.int32))
    caps = np.arange(1, 33, dtype=np.int32)
    if capacities == "traced":
        hlo = _kernel_hlo(ops.skewed_bucket, hashes,
                          _on(one_chip, jax.ShapeDtypeStruct((32,), jnp.int32)))
    else:
        fixed = jnp.asarray(caps) if capacities == "jnp" else caps
        hlo = _kernel_hlo(lambda h: ops.skewed_bucket(h, fixed), hashes)
    assert "tpu_custom_call" in hlo


def test_granite_decode_step_fits_one_chip(one_chip):
    cfg = dataclasses.replace(get_config("granite-3-8b"), n_layers=4)
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: init_decode_state(cfg, 8, 4096))
    tok = jax.ShapeDtypeStruct((8,), jnp.int32)
    compiled = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        *_on(one_chip, (params, state, tok))).compile()
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert m.alias_size_in_bytes > 0          # the cache updates in place
    assert peak < V5E_HBM_BYTES, peak
