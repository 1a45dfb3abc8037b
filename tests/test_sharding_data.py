"""Sharding rule engine (pure logic via a stub mesh) + data pipeline."""
import numpy as np
import pytest
from hypothesis import given, strategies as st

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import get_bundle, get_reduced
from repro.data.pipeline import FeederPlacement, SyntheticCorpus
from repro.runtime.sharding import _spec_for, axis_rules


class StubMesh:
    """Duck-typed mesh for the pure PartitionSpec logic."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)
        self.devices = np.empty(tuple(shape.values()))


MESH = StubMesh({"data": 16, "model": 16})
MESH3 = StubMesh({"pod": 2, "data": 16, "model": 16})
RULES = {"embed": ("data",), "heads": ("model",), "vocab": ("model",),
         "batch": ("pod", "data"), "layers": None}


def test_spec_basic():
    spec = _spec_for((4096, 6144), ("embed", "heads"), MESH, RULES, None)
    assert spec == P("data", "model")


def test_spec_divisibility_fallback():
    # 49155 not divisible by 16 -> replicated on that dim
    spec = _spec_for((49155, 4096), ("vocab", "embed"), MESH, RULES, None)
    assert spec == P(None, "data")


def test_spec_duplicate_axis_dropped():
    rules = {"a": ("model",), "b": ("model",)}
    spec = _spec_for((64, 64), ("a", "b"), MESH, rules, None)
    assert spec == P("model", None)      # model axis used once only


def test_spec_multi_axis_prefix_fallback():
    # batch=16 divisible by pod(2) but not pod*data(32) -> prefix ("pod",)
    spec = _spec_for((16, 128), ("batch", None), MESH3, RULES, None)
    assert spec == P("pod", None)


def test_axis_rules_kv_fallback():
    cfg = get_bundle("granite-3-8b").model     # kv=8 < model 16
    rules = axis_rules(cfg, MESH, get_bundle("granite-3-8b").mesh)
    assert rules["kv_heads_cache"] is None
    assert rules["cache_seq"] == ("model",)
    cfg_w = get_bundle("whisper-medium").model  # kv=16 == model 16
    rules_w = axis_rules(cfg_w, MESH, get_bundle("whisper-medium").mesh)
    assert rules_w["kv_heads_cache"] == ("model",)


@pytest.mark.parametrize("arch,mesh_shape,seq_axes,head_axes", [
    ("granite-3-8b", (2, 16), ("data", "model"), None),   # kv 8 < model 16
    ("whisper-medium", (2, 4), "data", "model")])         # kv 16 on model 4
def test_cache_shardings_shard_sequence_by_name(arch, mesh_shape, seq_axes,
                                                head_axes):
    """A batch the data axis does not divide: each attention cache shards
    its sequence dimension, found by its name in ``cache_axes``, in the
    head-major layout (layers, batch, kv heads, seq, head_dim)."""
    import dataclasses
    from jax.sharding import AbstractMesh
    from repro.models.model import init_decode_state
    from repro.runtime.sharding import cache_shardings
    bundle = get_bundle(arch)
    cfg = dataclasses.replace(bundle.model, n_layers=2)
    mesh = AbstractMesh(mesh_shape, ("data", "model"))
    state = jax.eval_shape(lambda: init_decode_state(cfg, 1, 4096))
    sh = cache_shardings(cfg, mesh, bundle.mesh, state, batch=1)
    for kv in ("k", "v"):
        assert state["cache"]["sub0"][kv].shape[3] == 4096
        assert sh["cache"]["sub0"][kv].spec == P(None, None, head_axes,
                                                 seq_axes, None)


def test_shardings_for_on_host_mesh():
    """End-to-end sharding build on the 1-device host mesh — the same code
    path the 256/512-chip dry-run uses."""
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.sharding import param_shardings
    mesh = make_host_mesh()
    cfg = get_reduced("granite-3-8b")
    sh = param_shardings(cfg, mesh, get_bundle("granite-3-8b").mesh)
    leaves = jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec"))
    assert all(hasattr(s, "spec") for s in leaves)


# --------------------------------------------------------------------------
# data pipeline
# --------------------------------------------------------------------------

@given(seed=st.integers(0, 1000), idx=st.integers(0, 10_000))
def test_corpus_index_addressable(seed, idx):
    c = SyntheticCorpus(256, 8, seed=seed)
    a, b = c.sample(idx), c.sample(idx)
    assert (a["tokens"] == b["tokens"]).all()
    assert a["tokens"].min() >= 1 and a["tokens"].max() < 256


def test_feeder_placement_balances_readers():
    fp = FeederPlacement(n_feeders=4, n_shards=16, replica=2, seed=0)
    # 16 concurrent grains on distinct shards: least-loaded replica choice
    # keeps the max-readers-per-feeder near ceil(16/4)
    assert fp.max_concurrent_readers(list(range(16))) <= 6
    # all on ONE shard: only its r=2 replicas can serve (paper's p1 case)
    assert fp.max_concurrent_readers([3] * 16) >= 8


def test_feeder_contention_probabilities_match_model():
    fp = FeederPlacement(4, 8, replica=2)
    assert fp.expected_collision_prob(same_shard=True) == pytest.approx(0.5)
    assert fp.expected_collision_prob(same_shard=False) == pytest.approx(0.25)


def test_batch_block_matches_batch_and_reuses_buffer():
    """The grain fast path: batch_block fills a preallocated [G, B, seq]
    buffer with exactly the samples batch() would stack, and reuses the
    same buffer for same-shape requests (no per-step reallocation)."""
    c = SyntheticCorpus(256, 8, seed=4)
    idx = np.arange(12).reshape(3, 4)
    block = c.batch_block(idx)
    assert block["tokens"].shape == (3, 4, 8)
    for g in range(3):
        ref = c.batch(list(idx[g]))
        assert (block["tokens"][g] == ref["tokens"]).all()
        assert (block["labels"][g] == ref["labels"]).all()
    again = c.batch_block(idx + 100)
    assert again["tokens"] is block["tokens"]          # buffer reuse
    other = c.batch_block(np.arange(8).reshape(2, 4))
    assert other["tokens"] is not block["tokens"]      # per-shape buffers


def test_load_stacked_matches_per_grain_loads():
    from repro.data.grains import Grain, GrainSource
    c = SyntheticCorpus(256, 8, seed=5)
    src = GrainSource(c, grain_batch=4)
    grains = [Grain(0, i * 4, 4) for i in range(3)]
    stacked = src.load_stacked(grains)
    for g_i, g in enumerate(grains):
        ref = src.load(g)
        assert (stacked["tokens"][g_i] == ref["tokens"]).all()
        assert (stacked["labels"][g_i] == ref["labels"]).all()
    with pytest.raises(ValueError):
        src.load_stacked([Grain(0, 0, 3)])             # ragged grain
