"""Compile-only tests for a described TPU v5e.

Nothing runs: each test lowers a kernel or step at real widths for one chip
of a described ``v5e:2x2`` topology and reads the compiled program. This is
what catches a block layout that Mosaic refuses or a step that does not fit
the chip's 16 GiB, which the CPU interpret-mode tests never see.
"""
import dataclasses
import math
import re

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


_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\((.*)")
_NOT_MATERIALIZED = {"parameter", "get-tuple-element", "bitcast"}


def _materialized(hlo: str, min_elems: int):
    """(name, opcode, update elements) of every value of ``min_elems`` or
    more that the program writes to memory: the instructions outside fused
    computations, each fusion named by its root's opcode; ``update`` is a
    dynamic-update-slice's update size, else None."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line == "}":
            cur = None
        elif cur is not None and (m := _INSTR.match(line)):
            cur.append(m.groups())
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo))
    elems = {name: math.prod(int(d) for d in dims.split(",") if d)
             for instrs in comps.values() for name, dims, _, _ in instrs}

    def update_of(operands):
        return elems.get(re.findall(r"%([\w.\-]+)", operands)[1])

    out = []
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        for name, _, op, rest in instrs:
            if elems[name] < min_elems or op in _NOT_MATERIALIZED:
                continue
            if op == "fusion":
                callee = re.search(r"calls=%([\w.\-]+)", rest).group(1)
                _, _, op, rest = comps[callee][-1]      # the fusion's root
            update = update_of(rest) if op == "dynamic-update-slice" else None
            out.append((name, op, update))
    return out


def test_granite_decode_step_writes_one_token_in_place(one_chip):
    """The serving cell's decode step (granite widths, batch 32, 2304 slots,
    cache donated): the cache rides in the layer scan's carry, each layer
    writes its one token into it in place, and nothing of one layer's K or
    V or more is copied, sliced out or held as scratch."""
    cfg = dataclasses.replace(get_config("granite-3-8b"), n_layers=2)
    batch, slots = 32, 2304
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: init_decode_state(cfg, batch, slots))
    tok = jax.ShapeDtypeStruct((batch,), jnp.int32)
    compiled = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        *_on(one_chip, (params, state, tok))).compile()
    acfg = cfg.attention
    layer_elems = batch * slots * acfg.n_kv_heads * acfg.head_dim
    layer_bytes = 2 * layer_elems                             # bf16
    big = _materialized(compiled.as_text(), layer_elems)
    assert big, "the cache writes were not found"
    for name, op, update in big:
        assert op == "dynamic-update-slice" and update < layer_elems, \
            (name, op, update)
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < layer_bytes, m.temp_size_in_bytes
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(state["cache"]))
    assert m.alias_size_in_bytes >= cache_bytes


def test_moe_grouped_dispatch_compiles_at_granite_moe_widths(one_chip):
    """granite-moe-1b-a400m's expert layer as the training cell runs it (one
    sequence of 4096 tokens, 32 experts of 512, top 8, every expert able to
    take every token): forward and backward compile for the chip as grouped
    products, and no (B, E*cap, D) slot buffer is materialized."""
    from repro.configs.base import MoEConfig
    from repro.models.moe import is_dropless, moe_layer
    s, d, f, e, k = 4096, 1024, 512, 32, 8
    cfg = MoEConfig(n_experts=e, top_k=k, capacity_factor=e / k)
    assert is_dropless(cfg, s)
    p = {"router": jax.ShapeDtypeStruct((d, e), jnp.float32),
         "w_gate": jax.ShapeDtypeStruct((e, d, f), jnp.bfloat16),
         "w_up": jax.ShapeDtypeStruct((e, d, f), jnp.bfloat16),
         "w_down": jax.ShapeDtypeStruct((e, f, d), jnp.bfloat16)}
    x = jax.ShapeDtypeStruct((1, s, d), jnp.bfloat16)

    def loss(p, x):
        out, aux, _ = moe_layer(p, x, cfg)
        return jnp.sum(out.astype(jnp.float32)) + aux

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *_on(one_chip, (p, x))).compile().as_text()
    assert "ragged-dot" in hlo
    slot_buffer = e * s * d                    # capacity S per expert
    assert not _materialized(hlo, slot_buffer)
    assert _materialized(hlo, s * k * d)       # the sorted rows are there


# grain_accumulate's temporary bytes for the MoE training cell when the
# chunked attention visited every (query block, key block) tile
MOE_GRAIN_TEMP_EVERY_TILE = 2_656_546_816


def test_granite_moe_grain_accumulate_compiles_in_no_more_temp(one_chip):
    """granite-moe-1b-a400m's grain_accumulate as its training cell runs it
    (12 layers at published widths, dropless, one sequence of 4096 tokens,
    whose attention takes the chunked path) compiles for one chip, and
    computing only attention's open tiles takes no more temporary memory
    than visiting every tile did."""
    from repro.configs import get_bundle
    from repro.runtime.train_loop import grain_acc_init, make_grain_accumulate
    base = get_config("granite-moe-1b-a400m")
    cfg = dataclasses.replace(base, n_layers=12, moe=dataclasses.replace(
        base.moe, capacity_factor=base.moe.n_experts / base.moe.top_k))
    bundle = get_bundle("granite-moe-1b-a400m").replace(model=cfg)
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    acc = jax.eval_shape(grain_acc_init, params)
    rows = jax.ShapeDtypeStruct((1, 1, 4096), jnp.int32)
    compiled = make_grain_accumulate(cfg, bundle).lower(*_on(
        one_chip, (params, acc, {"tokens": rows, "labels": rows}))).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= MOE_GRAIN_TEMP_EVERY_TILE, temp


_COLLECTIVE = re.compile(r"= \w+\[([\d,]*)\]\S* (all-gather|all-reduce|all-to-all|"
                         r"collective-permute|reduce-scatter)(?:-start)?\(")


@pytest.mark.parametrize("batch,seq,capacity_factor", [(4, 4096, 4.0), (32, 1, 1.25)],
                         ids=["train", "decode"])
def test_moe_layer_keeps_rows_in_their_data_shard(batch, seq, capacity_factor):
    """granite-moe-1b-a400m's expert layer with its batch split over the
    four chips of a described v5e:2x2 and the weights on every chip: a
    training call (forward and backward) and a decode step, both with no
    choice dropped. A call of several sequences sorts each on its own, so
    no collective moves a shard's routed rows."""
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs.base import MoEConfig
    from repro.models.moe import is_dropless, moe_layer
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    d, f, e, k = 1024, 512, 32, 8
    cfg = MoEConfig(n_experts=e, top_k=k, capacity_factor=capacity_factor)
    assert is_dropless(cfg, seq)
    everywhere = NamedSharding(mesh, P())
    p = {"router": jax.ShapeDtypeStruct((d, e), jnp.float32, sharding=everywhere),
         **{n: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=everywhere)
            for n, shape in (("w_gate", (e, d, f)), ("w_up", (e, d, f)),
                             ("w_down", (e, f, d)))}}
    x = jax.ShapeDtypeStruct((batch, seq, d), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))

    def loss(p, x):
        out, aux, _ = moe_layer(p, x, cfg)
        return jnp.sum(out.astype(jnp.float32)) + aux

    fn = jax.grad(loss, argnums=(0, 1)) if seq > 1 else (
        lambda p, x: moe_layer(p, x, cfg)[0])
    hlo = jax.jit(fn).lower(p, x).compile().as_text()
    shard_rows = batch // 4 * seq * k * d
    moved = [(op, dims) for dims, op in _COLLECTIVE.findall(hlo)
             if math.prod(int(n) for n in dims.split(",") if n) >= shard_rows]
    assert not moved, moved
