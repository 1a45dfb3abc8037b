"""Train-step factory + HeMT grain accumulation.

Two granularities:

* ``make_train_step`` — one jit-able global step (whole global batch in one
  program). This is what the multi-pod dry-run lowers: batch sharded over
  ("pod","data"), params per the bundle's sharding rules, AdamW fused in.

* ``make_grain_step`` / ``make_apply_step`` — HeMT-DP decomposition: a
  grain step accumulates loss/grads over one fixed-shape microbatch; the
  apply step consumes the (weighted) accumulated gradient at the barrier.
  The accumulation trip count is a *host-side* loop so each slice can run
  its own k_i (the paper's macrotask size) between barriers.

* ``make_grain_accumulate`` / ``grain_accumulate_cached`` — batched fast
  path: the stacked grains of a whole step ([G, grain_batch, seq]) are
  folded into one GrainAcc with a single jitted ``lax.scan`` dispatch
  instead of G Python-dispatched grain steps.  The step's grain count is
  fixed (global_batch // grain_batch), so the scan traces once per config;
  ``grain_accumulate_cached`` keys a module-level jit cache on the (frozen,
  hashable) config bundle so drivers built repeatedly — benchmarks sweeping
  modes, elastic restarts — reuse the compiled program.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchBundle, ModelConfig, TrainConfig
from repro.models.model import init_params, loss_and_stats
from repro.models.moe import MoEStats
from repro.optim.adamw import AdamWState, adamw_init, adamw_update
from repro.optim.compression import (
    CompressionState, compress_decompress, compression_init,
)
from repro.optim.schedule import warmup_cosine

Pytree = Any


class TrainState(NamedTuple):
    params: Pytree
    opt: AdamWState
    step: jnp.ndarray          # () int32
    ef: Pytree                 # compression error-feedback (possibly empty {})


def train_state_init(key, cfg: ModelConfig, bundle: ArchBundle) -> TrainState:
    params = init_params(key, cfg)
    moment_dtype = "bfloat16" if bundle.mesh.bf16_optimizer else "float32"
    opt = adamw_init(params, moment_dtype)
    ef: Pytree = {}
    if bundle.train.compression != "none":
        ef = compression_init(params).error
    return TrainState(params, opt, jnp.zeros((), jnp.int32), ef)


def _update(tc: TrainConfig, state: TrainState, grads: Pytree,
            ) -> Tuple[TrainState, jnp.ndarray, jnp.ndarray]:
    """One optimizer step on ``grads``: compression (with its error
    feedback), the warmup-cosine rate and AdamW. Returns (the new state,
    the gradient norm, the rate)."""
    ef = state.ef
    if tc.compression != "none":
        sent, new_cs = compress_decompress(
            grads, CompressionState(ef), scheme=tc.compression)
        grads, ef = sent, new_cs.error
    lr = warmup_cosine(state.step, peak_lr=tc.lr,
                       warmup_steps=tc.warmup_steps,
                       total_steps=tc.total_steps)
    params, opt, gnorm = adamw_update(
        grads, state.opt, state.params, lr=lr, beta1=tc.beta1,
        beta2=tc.beta2, weight_decay=tc.weight_decay,
        grad_clip=tc.grad_clip)
    return TrainState(params, opt, state.step + 1, ef), gnorm, lr


def make_train_step(cfg: ModelConfig, bundle: ArchBundle, *, constrain=None,
                    ) -> Callable[[TrainState, Dict[str, jnp.ndarray]],
                                  Tuple[TrainState, Dict[str, jnp.ndarray]]]:
    """constrain: optional residual-stream sharding hook (sequence-parallel
    saved activations — see runtime.sharding.make_activation_constraint)."""
    tc = bundle.train
    remat = bundle.mesh.remat

    def train_step(state: TrainState, batch: Dict[str, jnp.ndarray],
                   ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        (loss, moe), grads = jax.value_and_grad(loss_and_stats, has_aux=True)(
            state.params, batch, cfg, remat=remat, constrain=constrain)
        new_state, gnorm, lr = _update(tc, state, grads)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr, "moe": moe}
        return new_state, metrics

    return train_step


# --------------------------------------------------------------------------
# HeMT-DP grain decomposition
# --------------------------------------------------------------------------

class GrainAcc(NamedTuple):
    grads: Pytree
    loss_sum: jnp.ndarray
    n: jnp.ndarray             # grains accumulated
    moe: Optional[MoEStats]    # MoE dispatch rows summed over grains;
                               # None before the first grain


def grain_acc_init(params: Pytree) -> GrainAcc:
    return GrainAcc(
        grads=jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        loss_sum=jnp.zeros(()), n=jnp.zeros((), jnp.int32), moe=None)


def _fold(acc: GrainAcc, loss, moe: MoEStats, grads) -> GrainAcc:
    """acc with one grain's loss, MoE rows and gradient added."""
    return GrainAcc(
        grads=jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc.grads, grads),
        loss_sum=acc.loss_sum + loss, n=acc.n + 1,
        moe=moe if acc.moe is None else acc.moe.plus(moe))


def make_grain_step(cfg: ModelConfig, bundle: ArchBundle, *,
                    jit: bool = True) -> Callable:
    remat = bundle.mesh.remat

    def grain_step(params: Pytree, acc: GrainAcc,
                   grain: Dict[str, jnp.ndarray]) -> GrainAcc:
        (loss, moe), grads = jax.value_and_grad(loss_and_stats, has_aux=True)(
            params, grain, cfg, remat=remat)
        return _fold(acc, loss, moe, grads)

    return jax.jit(grain_step) if jit else grain_step


def make_grain_accumulate(cfg: ModelConfig, bundle: ArchBundle, *,
                          jit: bool = True) -> Callable:
    """(params, acc, grains[G, ...]) -> acc after folding all G grains.

    Semantically identical to calling ``grain_step`` G times in stacking
    order, but issues one jitted dispatch (lax.scan over the leading grain
    axis) — the O(grains) Python-dispatch overhead of the per-grain loop
    disappears from the step hot path."""
    remat = bundle.mesh.remat

    def grain_accumulate(params: Pytree, acc: GrainAcc,
                         grains: Dict[str, jnp.ndarray]) -> GrainAcc:
        def body(carry: GrainAcc, grain: Dict[str, jnp.ndarray]):
            (loss, moe), grads = jax.value_and_grad(loss_and_stats, has_aux=True)(
                params, grain, cfg, remat=remat)
            return _fold(carry, loss, moe, grads), None

        if acc.moe is None:   # the scan's carry keeps one structure
            acc = acc._replace(moe=MoEStats.zeros())
        out, _ = jax.lax.scan(body, acc, grains)
        return out

    # the incoming acc is a fresh zero tree the caller never reads again:
    # donating it lets the fp32 grad accumulator update in place
    return (jax.jit(grain_accumulate, donate_argnums=(1,)) if jit
            else grain_accumulate)


_GRAIN_ACC_CACHE: Dict[Any, Callable] = {}


def grain_accumulate_cached(cfg: ModelConfig, bundle: ArchBundle) -> Callable:
    """Module-level cache of jitted grain-accumulate functions, keyed by the
    frozen (cfg, bundle) pair: every driver with the same config shares one
    traced program."""
    key = (cfg, bundle)
    fn = _GRAIN_ACC_CACHE.get(key)
    if fn is None:
        fn = _GRAIN_ACC_CACHE[key] = make_grain_accumulate(cfg, bundle)
    return fn


def make_apply_step(cfg: ModelConfig, bundle: ArchBundle, *,
                    jit: bool = True) -> Callable:
    """Barrier step: mean the accumulated grads over the *global* grain
    count (HeMT slices contribute different k_i; the denominator is the
    total, so skewing never biases the gradient) and apply AdamW."""
    tc = bundle.train

    def apply_step(state: TrainState, acc: GrainAcc,
                   total_grains: jnp.ndarray,
                   ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        denom = jnp.maximum(total_grains.astype(jnp.float32), 1.0)
        grads = jax.tree.map(lambda g: g / denom, acc.grads)
        new_state, gnorm, lr = _update(tc, state, grads)
        metrics = {"loss": acc.loss_sum / denom, "grad_norm": gnorm, "lr": lr,
                   "moe": acc.moe}
        return new_state, metrics

    # donating acc lets its fp32 grads back the new fp32 moments
    return jax.jit(apply_step, donate_argnums=(1,)) if jit else apply_step
