"""Serving: prefill/decode step factories + HeMT continuous batching.

``make_serve_step`` / ``make_prefill_step`` are what the decode/prefill
dry-run shapes lower. ``HeMTBatcher`` is the paper's §5.1 estimator applied
to replicas: request batches are sized proportional to AR(1)-estimated
per-replica decode throughput, so heterogeneous replicas (contended hosts,
burstable capacity) reach their batch deadlines together — the serving
analogue of macrotask skewing.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.engine import AdaptivePlan
from repro.core.estimators import ARSpeedEstimator
from repro.core.partitioner import proportional_split, even_split
from repro.models.model import decode_step, prefill

Pytree = Any


def make_serve_step(cfg: ModelConfig) -> Callable:
    """serve_step(params, state, tokens (B,), [enc_out]) ->
    (greedy next_tokens (B,), logits (B,V), new state)."""

    def serve_step(params: Pytree, state: Pytree, tokens: jnp.ndarray,
                   enc_out: Optional[jnp.ndarray] = None):
        logits, new_state = decode_step(params, state, tokens, cfg,
                                        enc_out=enc_out)
        with jax.named_scope("decode_sample"):
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, logits, new_state

    return serve_step


def make_prefill_step(cfg: ModelConfig, max_len: int) -> Callable:
    """prefill_step(params, tokens (B,S), [enc_feats]) ->
    (first sampled token (B,), decode state)."""

    def prefill_step(params: Pytree, tokens: jnp.ndarray,
                     enc_feats: Optional[jnp.ndarray] = None):
        logits, state = prefill(params, tokens, cfg, max_len,
                                enc_feats=enc_feats)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), state

    return prefill_step


# --------------------------------------------------------------------------
# HeMT continuous batching across replicas
# --------------------------------------------------------------------------

@dataclass
class ReplicaState:
    name: str
    active: int = 0                  # requests currently decoding
    tokens_done: int = 0


@dataclass
class DispatchRecord:
    round: int
    shares: Dict[str, int]
    predicted_finish: Dict[str, float]


class HeMTBatcher:
    """Sizes per-replica request batches ∝ estimated decode throughput.

    `observe(replica, tokens, seconds)` feeds the same AR(1) estimator the
    trainer uses (§5.1 — per job class, here per model). `dispatch(n)`
    splits n requests; homogeneous mode (`mode='even'`) is the HomT-like
    baseline."""

    def __init__(self, replicas: Sequence[str], *, alpha: float = 0.3,
                 mode: str = "hemt", min_share: int = 0):
        self.replicas = list(replicas)
        self.estimator = ARSpeedEstimator(alpha=alpha)
        self.mode = mode
        self.min_share = min_share
        self.log: List[DispatchRecord] = []
        self._round = 0

    def observe(self, replica: str, tokens: int, seconds: float) -> None:
        if tokens > 0 and seconds > 0:
            self.estimator.observe(replica, tokens, seconds)

    def dispatch(self, n_requests: int) -> Dict[str, int]:
        n = len(self.replicas)
        if self.mode == "even" or not self.estimator.known():
            shares = even_split(n_requests, n)
        else:
            speeds = self.estimator.speeds(self.replicas)
            shares = proportional_split(n_requests, speeds,
                                        min_share=self.min_share)
        speeds = self.estimator.speeds(self.replicas)
        pred = {r: (s / v if v > 0 else float("inf"))
                for r, s, v in zip(self.replicas, shares, speeds)}
        out = dict(zip(self.replicas, shares))
        self.log.append(DispatchRecord(self._round, out, pred))
        self._round += 1
        return out

    def resize(self, replicas: Sequence[str]) -> None:
        gone = set(self.replicas) - set(replicas)
        for g in gone:
            self.estimator.forget(g)
        self.replicas = list(replicas)

    def plan(self, **kwargs) -> AdaptivePlan:
        """An :class:`~repro.core.engine.AdaptivePlan` sharing this
        batcher's AR(1) state.  The fleet serving scenario
        (:mod:`repro.runtime.serving`) attaches one per batch job, so
        every decode split is sized from the same estimates round-based
        ``dispatch`` uses and every finished batch feeds the estimator
        back through the resident calendar's barrier observations."""
        return AdaptivePlan(self.estimator, **kwargs)

    def straggling(self, factor: float = 2.0) -> List[str]:
        """Replicas whose estimated speed has fallen ``factor``x below
        the median estimate — the serving-side speculation trigger.
        Round drivers (:func:`repro.runtime.serving.run_round`) hedge
        these with duplicate decode attempts via
        :class:`~repro.core.speculation.SpeculativeCopies`."""
        if factor < 1.0:
            raise ValueError("straggler factor must be >= 1.0")
        if not self.estimator.known():
            return []
        speeds = self.estimator.speeds(self.replicas)
        ordered = sorted(speeds)
        mid = len(ordered) // 2
        median = ordered[mid] if len(ordered) % 2 else \
            0.5 * (ordered[mid - 1] + ordered[mid])
        return [r for r, v in zip(self.replicas, speeds)
                if v * factor < median]

    def predicted_sync_delay(self, shares: Dict[str, int]) -> float:
        speeds = dict(zip(self.replicas, self.estimator.speeds(self.replicas)))
        times = [shares[r] / speeds[r] for r in self.replicas
                 if shares.get(r, 0) > 0]
        return (max(times) - min(times)) if times else 0.0
