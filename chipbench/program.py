"""The system under test, as the benchmark drives it.

This is the one module of ``chipbench`` that imports the program
(``src/repro``): its model configuration, the HeMT-DP trainer and the
serving steps. The weights, the optimizer's zero state and the token rows
come from the benchmark (``weights.py``); the program receives them as
inputs.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from repro.configs import get_bundle  # noqa: E402
from repro.configs.base import (  # noqa: E402
    AttentionConfig, ModelConfig, MoEConfig, TrainConfig, padded_vocab_size,
)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.optim.adamw import AdamWState  # noqa: E402
from repro.runtime.hemt_driver import HeMTTrainer, SliceSpec  # noqa: E402
from repro.runtime.serve_loop import make_prefill_step, make_serve_step  # noqa: E402
from repro.runtime.train_loop import TrainState  # noqa: E402

__all__ = ["enable_compile_cache", "model_config", "Program"]


def model_config(c: dict, name: str) -> ModelConfig:
    """The program's ModelConfig for a config file, key by key."""
    m = W.dims(c)
    moe = None
    if m["experts"]:
        a = c["assumed"]
        moe = MoEConfig(n_experts=m["experts"], top_k=m["top_k"],
                        capacity_factor=float(a["capacity_factor"]),
                        aux_loss_weight=float(a["moe_aux_loss_weight"]))
    return ModelConfig(
        name=name, family="moe" if moe else "dense", n_layers=m["layers"],
        d_model=m["d"], d_ff=m["ff"], vocab_size=m["vocab"],
        attention=AttentionConfig(n_heads=m["heads"], n_kv_heads=m["kv_heads"],
                                  head_dim=m["head_dim"],
                                  rope_theta=float(c["rope_theta"])),
        moe=moe, norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), dtype="bfloat16")


LAYER_PATHS = {"norm1": ("norm1", "scale"), "wq": ("mixer", "wq"),
               "wk": ("mixer", "wk"), "wv": ("mixer", "wv"),
               "wo": ("mixer", "wo"), "norm2": ("norm2", "scale"),
               "router": ("ffn", "router"), "w_gate": ("ffn", "w_gate"),
               "w_up": ("ffn", "w_up"), "w_down": ("ffn", "w_down")}


class RowSource:
    """Stands in for the trainer's corpus: sample ``i`` is token row
    (seed, i) of ``weights.rows``, as the reference makes it again."""

    def __init__(self, seed: int, vocab: int, seq_len: int):
        self.seed, self.vocab, self.seq_len = seed, vocab, seq_len

    def batch_block(self, indices) -> dict:
        idx = np.asarray(indices)
        toks = W.rows(self.seed, W.TRAIN_STREAM, idx.reshape(-1),
                      self.seq_len + 1, self.vocab)
        toks = toks.reshape(*idx.shape, self.seq_len + 1)
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}


class Program:
    """One configuration of the program, with jitted helpers that move
    between the benchmark's flat weights and the program's trees."""

    def __init__(self, c: dict, name: str):
        self.c, self.name = c, name
        self.cfg = model_config(c, name)
        if self.cfg.layer_period != 1:
            raise ValueError(f"{name}: layer period {self.cfg.layer_period} "
                             "is not mapped to the benchmark's weights")
        self.vocab = self.cfg.vocab_size
        self._params = jax.jit(lambda words: self.tree(W.make(c, words)))
        self._state = jax.jit(self._init_state)
        self._change = jax.jit(self._change_norms)
        self._norms = jax.jit(self.norms)

    # ---- trees ---------------------------------------------------------
    def tree(self, w: dict) -> dict:
        pv = padded_vocab_size(self.cfg)
        layer: dict = {}
        for k, (a, b) in LAYER_PATHS.items():
            if k in w:
                layer.setdefault(a, {})[b] = w[k]
        table = jnp.pad(w["embed"], ((0, pv - self.vocab), (0, 0)))
        return {"embed": {"table": table}, "stack": {"sub0": layer},
                "final_norm": {"scale": w["final_norm"]}}

    def norms(self, tree: dict) -> dict:
        """Norm of each leaf, per layer for stacked leaves, by the
        reference's names (``wq.0``, ``embed``, ...)."""
        sq = lambda x, axes: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                              axis=axes))
        out = {"embed": sq(tree["embed"]["table"][:self.vocab], None),
               "final_norm": sq(tree["final_norm"]["scale"], None)}
        layer = tree["stack"]["sub0"]
        for k, (a, b) in LAYER_PATHS.items():
            if a in layer and b in layer[a]:
                x = layer[a][b]
                per = sq(x, tuple(range(1, x.ndim)))
                for i in range(x.shape[0]):
                    out[f"{k}.{i}"] = per[i]
        return out

    def params(self, seed: int) -> dict:
        return self._params(W.seed_words(seed))

    def _init_state(self, words):
        p = self.tree(W.make(self.c, words))
        zeros = lambda t: jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), t)
        return TrainState(p, AdamWState(jnp.zeros((), jnp.int32), zeros(p), zeros(p)),
                          jnp.zeros((), jnp.int32), {})

    def _change_norms(self, params, words):
        p0 = self.tree(W.make(self.c, words))
        return self.norms(jax.tree.map(lambda a, b: a.astype(jnp.float32)
                                       - b.astype(jnp.float32), params, p0))

    def change_norms(self, params, seed: int) -> dict:
        return {k: float(v) for k, v in
                self._change(params, W.seed_words(seed)).items()}

    def leaf_norms(self, tree) -> dict:
        return {k: float(v) for k, v in self._norms(tree).items()}

    # ---- training ------------------------------------------------------
    def trainer(self, t: dict, seed: int):
        """The HeMT-DP trainer and its initial state, as
        ``repro.launch.train.build_trainer`` makes them, but with the
        benchmark's weights and rows and the traffic file's schedule."""
        bundle = get_bundle(self.c.get("program_arch", self.name)).replace(
            model=self.cfg,
            train=TrainConfig(lr=float(t["peak_lr"]),
                              warmup_steps=int(t["warmup_steps"]),
                              total_steps=int(t["schedule_steps"]),
                              weight_decay=float(t["weight_decay"]),
                              beta1=float(t["beta1"]), beta2=float(t["beta2"]),
                              grad_clip=float(t["grad_clip"])))
        slices = [SliceSpec(f"slice{i}", [(0.0, float(v))], grain_overhead=0.05)
                  for i, v in enumerate(t["slice_speeds"])]
        tr = HeMTTrainer(self.cfg, bundle, slices,
                         grain_batch=int(t["grain_batch"]),
                         global_batch=int(t["global_batch"]),
                         seq_len=int(t["seq_len"]), mode=t["mode"], seed=seed)
        tr.corpus = RowSource(seed, self.vocab, int(t["seq_len"]))
        tr.source.corpus = tr.corpus
        return tr, self._state(W.seed_words(seed))

    # ---- serving -------------------------------------------------------
    def serve_steps(self, t: dict):
        prefill = jax.jit(make_prefill_step(self.cfg, int(t["cache_len"])))
        decode = jax.jit(make_serve_step(self.cfg), donate_argnums=(1,))
        return prefill, decode
