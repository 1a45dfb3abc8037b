"""Logical-axis -> mesh sharding with automatic divisibility fallback.

Model code annotates every parameter/cache leaf with *logical* axis names
(`models.model.params_axes`, `transformer.cache_axes`). This module maps
them onto the production mesh per the ArchBundle's MeshConfig:

  heads / kv_heads / mlp / vocab / expert / ssm_inner / ssm_conv -> "model"  (TP/EP)
  embed         -> ("pod","data") under FSDP (ZeRO-3), else replicated
  batch         -> ("pod","data")   (pure DP across pods — DCN only carries
                                     the gradient all-reduce, per DESIGN §7)
  cache_seq     -> "model" only when kv heads don't divide the model axis
  seq (activations) -> "data" for long-context decode (sequence parallelism)
  layers        -> never sharded (scan axis)

Every mapping is validated against the actual leaf dim: if the mesh-axis
product doesn't divide it (e.g. deepseek's 56 heads on a 16-way axis — the
flattened heads*head_dim dim *is* divisible; granite's 49155 vocab is padded
upstream), the rule falls back to replication for that leaf instead of
failing to lower. Fallbacks are recorded so the dry-run can report them.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import MeshConfig, ModelConfig

Pytree = Any

MODEL_AXES = ("heads", "kv_heads", "mlp", "vocab", "expert", "ssm_inner",
              "ssm_conv", "kv_heads_cache")


def _data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_rules(cfg: ModelConfig, mesh: Mesh, mesh_cfg: MeshConfig,
               ) -> Dict[str, Optional[Tuple[str, ...]]]:
    """Logical-name -> mesh-axes tuple (None = replicated)."""
    data = _data_axes(mesh)
    # FSDP axes: by default exclude "pod" so parameter all-gathers stay on
    # ICI and the DCN only carries the per-step gradient all-reduce
    # (EXPERIMENTS §Perf cell C measures the difference)
    fsdp_axes = data if mesh_cfg.fsdp_pod else tuple(
        a for a in data if a != "pod")
    rules: Dict[str, Optional[Tuple[str, ...]]] = {
        "layers": None,
        "batch": data,
        "embed": fsdp_axes if mesh_cfg.fsdp else None,
        "seq": ("data",) if mesh_cfg.sequence_parallel else None,
    }
    for name in MODEL_AXES:
        rules[name] = ("model",)
    # (Refuted hypothesis, kept sharded: replicating kv projections when
    # n_kv_heads < model-axis size does NOT remove the pair-wise retiling
    # all-gathers — they come from attention-internal activation layouts,
    # not the weights. See EXPERIMENTS §Perf cell C iteration C2.)
    a = cfg.attention
    model_size = mesh.shape["model"] if "model" in mesh.axis_names else 1
    # KV-cache fallback: the cache layout is (..., n_kv_heads, seq, head_dim)
    # with the *head count* as its own dim — when it doesn't divide the
    # model axis (GQA kv=8 or 2 on a 16-way axis), shard the cache's
    # sequence dim instead (paged-KV style; XLA inserts the ring-update
    # collectives around the dynamic-update-slice).
    if a is not None and a.n_kv_heads % max(model_size, 1) != 0:
        rules["kv_heads_cache"] = None
        rules["cache_seq"] = ("model",)
    else:
        rules["cache_seq"] = None
    # SSM decode state: (layers, batch, heads, P, N) — shard heads on model
    rules["ssm_heads_cache"] = ("model",)
    return rules


class ShardingReport:
    """Collects per-leaf fallbacks for the dry-run log."""

    def __init__(self):
        self.fallbacks: List[str] = []

    def note(self, path: str, dim: int, size: int, axes: Tuple[str, ...]):
        self.fallbacks.append(
            f"{path} dim{dim}={size} not divisible by {axes} -> replicated")


def _spec_for(shape: Tuple[int, ...], names: Tuple, mesh: Mesh,
              rules: Dict[str, Optional[Tuple[str, ...]]],
              report: Optional[ShardingReport], path: str = "") -> P:
    used: set = set()
    parts: List[Optional[Tuple[str, ...]]] = []
    for d, name in enumerate(names):
        if name is None:
            parts.append(None)
            continue
        axes = rules.get(name)
        if axes is None:
            parts.append(None)
            continue
        axes = tuple(a for a in axes if a in mesh.axis_names and a not in used)
        if not axes:
            parts.append(None)
            continue
        prod = int(np.prod([mesh.shape[a] for a in axes]))
        if d >= len(shape) or shape[d] % prod != 0:
            # divisibility fallback: try a prefix of the axes tuple
            while axes and (d >= len(shape) or shape[d] % int(
                    np.prod([mesh.shape[a] for a in axes])) != 0):
                axes = axes[:-1]
            if not axes:
                if report is not None and d < len(shape):
                    parts.append(None)
                    report.note(path, d, shape[d], tuple(rules.get(name) or ()))
                    continue
                parts.append(None)
                continue
        used.update(axes)
        parts.append(axes if len(axes) > 1 else axes[0])
    return P(*parts)


def shardings_for(abstract: Pytree, axes_tree: Pytree, mesh: Mesh,
                  rules: Dict[str, Optional[Tuple[str, ...]]],
                  report: Optional[ShardingReport] = None) -> Pytree:
    """NamedSharding pytree for `abstract` (ShapeDtypeStruct tree) given the
    logical-axes tree (same structure, leaves = tuples of names)."""
    is_names = lambda t: isinstance(t, tuple) and all(
        n is None or isinstance(n, str) for n in t)

    flat_ax, _ = jax.tree_util.tree_flatten_with_path(axes_tree, is_leaf=is_names)
    flat_ab = jax.tree_util.tree_flatten(abstract)[0]
    assert len(flat_ax) == len(flat_ab), (len(flat_ax), len(flat_ab))
    out = []
    for (path, names), leaf in zip(flat_ax, flat_ab):
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        spec = _spec_for(tuple(leaf.shape), names, mesh, rules, report, pstr)
        out.append(NamedSharding(mesh, spec))
    treedef = jax.tree_util.tree_structure(abstract)
    return jax.tree_util.tree_unflatten(treedef, out)


def make_activation_constraint(mesh: Mesh, mesh_cfg: MeshConfig,
                               batch: int, seq: int):
    """Activation sharding hook, by kind:

      residual — (B,S,D): batch over ("pod","data"), seq over "model" when
                 sequence_parallel (Megatron-SP: cuts the saved scan-carry
                 stack by the model-axis size),
      hidden   — (B,S,D) before the unembed matmul: batch-sharded, rest
                 replicated (stops GSPMD from gathering the global batch
                 to shard the d_model contraction),
      logits   — (B,S,V): batch over data, vocab over "model" (keeps the
                 fp32 loss math fully sharded).

    Returns fn(x, kind="residual") or None when batch doesn't divide."""
    data = _data_axes(mesh)
    dprod = int(np.prod([mesh.shape[a] for a in data]))
    if batch % dprod != 0:
        return None
    dspec = data if len(data) > 1 else data[0]
    seq_ok = (mesh_cfg.sequence_parallel and "model" in mesh.axis_names
              and seq % mesh.shape["model"] == 0)
    has_model = "model" in mesh.axis_names
    specs = {
        "residual": P(dspec, "model" if seq_ok else None, None),
        "hidden": P(dspec, None, None),
        "logits": P(dspec, None, "model" if has_model else None),
        # (B, E, cap, D): experts over "model" = the EP all-to-all layout
        "moe_buffer": P(dspec, "model" if has_model else None, None, None),
        # (B, H, P, N) SSD carry: heads over "model" (the scan-saved state
        # stack is the dominant buffer for big hybrid models)
        "ssm_state": P(dspec, "model" if has_model else None, None, None),
    }
    _checked_dim = {"logits": -1, "moe_buffer": 1, "ssm_state": 1}

    def constrain(h, kind: str = "residual"):
        spec = specs[kind]
        d = _checked_dim.get(kind)
        if d is not None and spec[d] is not None \
                and h.shape[d] % mesh.shape["model"] != 0:
            spec = P(*([dspec] + [None] * (h.ndim - 1)))
        return jax.lax.with_sharding_constraint(h, NamedSharding(mesh, spec))

    return constrain


# --------------------------------------------------------------------------
# top-level builders
# --------------------------------------------------------------------------

def param_shardings(cfg: ModelConfig, mesh: Mesh, mesh_cfg: MeshConfig,
                    report: Optional[ShardingReport] = None) -> Pytree:
    from repro.models.model import init_params, params_axes
    abstract = jax.eval_shape(lambda k: init_params(k, cfg),
                              jax.random.PRNGKey(0))
    rules = axis_rules(cfg, mesh, mesh_cfg)
    return shardings_for(abstract, params_axes(cfg), mesh, rules, report)


def train_state_shardings(cfg: ModelConfig, mesh: Mesh, mesh_cfg: MeshConfig,
                          state_abstract: Pytree,
                          report: Optional[ShardingReport] = None) -> Pytree:
    """Shardings for a TrainState: params + mirrored opt moments; scalars
    replicated. Works off the abstract state from eval_shape."""
    from repro.models.model import params_axes
    rules = axis_rules(cfg, mesh, mesh_cfg)
    pax = params_axes(cfg)
    replicated = NamedSharding(mesh, P())

    def build(field_name: str, sub_abstract: Pytree) -> Pytree:
        if field_name in ("params", "mu", "nu"):
            return shardings_for(sub_abstract, pax, mesh, rules, report)
        return jax.tree.map(lambda _: replicated, sub_abstract)

    st = state_abstract
    return type(st)(
        params=build("params", st.params),
        opt=type(st.opt)(step=replicated,
                         mu=build("mu", st.opt.mu),
                         nu=build("nu", st.opt.nu)),
        step=replicated,
        ef=jax.tree.map(lambda _: replicated, st.ef),
    )


def batch_shardings(cfg: ModelConfig, mesh: Mesh, mesh_cfg: MeshConfig,
                    batch_abstract: Dict[str, Any],
                    long_context: bool = False) -> Dict[str, Any]:
    """Inputs: batch dim over ("pod","data"); for long-context single-row
    batches, the sequence dim goes over "data" instead (SP)."""
    data = _data_axes(mesh)
    out = {}
    for k, v in batch_abstract.items():
        b = v.shape[0]
        prod = int(np.prod([mesh.shape[a] for a in data]))
        if b % prod == 0:
            spec = [data if len(data) > 1 else data[0]] + [None] * (v.ndim - 1)
        elif len(v.shape) > 1 and long_context and v.shape[1] % mesh.shape["data"] == 0:
            spec = [None, "data"] + [None] * (v.ndim - 2)
        else:
            spec = [None] * v.ndim
        out[k] = NamedSharding(mesh, P(*spec))
    return out


def cache_shardings(cfg: ModelConfig, mesh: Mesh, mesh_cfg: MeshConfig,
                    cache_abstract: Pytree, batch: int,
                    report: Optional[ShardingReport] = None) -> Pytree:
    """Decode-state shardings. Batch over ("pod","data") when divisible;
    otherwise (long_500k's batch=1) the cache sequence dim is sharded over
    "data" — sequence parallelism for the KV pages."""
    from repro.models.transformer import cache_axes
    rules = axis_rules(cfg, mesh, mesh_cfg)
    data = _data_axes(mesh)
    prod = int(np.prod([mesh.shape[a] for a in data]))
    if batch % prod != 0:
        rules["batch"] = None
        # shard KV pages over "data" (plus "model" too when the kv-head dim
        # can't use it) — sequence parallelism for the cache
        if rules.get("kv_heads_cache") is None:
            rules["cache_seq"] = ("data", "model")
        else:
            rules["cache_seq"] = ("data",)
    ax = cache_axes(cfg)
    # decode state = {"cache": ..., "length": scalar}
    state_axes = {"cache": ax, "length": ()}
    return shardings_for(cache_abstract, state_axes, mesh, rules, report)
