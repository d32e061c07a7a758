"""The jitted training step: loss, grads, clipping, optimizer, seeds.

Per-step quantization seeds follow the paper's re-randomization contract
(App. A item 2): a fresh uint32 pair derived from (base_seed, step,
microbatch) feeds every qlinear call site, which further mixes in
(layer, site) — rotations/SR re-randomize per-tensor per-microbatch.

Gradient accumulation splits the per-device batch into microbatches
(jax.lax.scan over microbatch slices) so huge global batches fit; each
microbatch gets its own quantization seed.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import scopes as SC
from repro.models import lm
from repro.optim import adamw, muon, schedules


class TrainState(NamedTuple):
    params: dict
    opt: object
    step: jax.Array


def step_seed(base_seed: int, step: jax.Array, micro: jax.Array | int = 0) -> jax.Array:
    s = jnp.asarray(step, jnp.uint32)
    m = jnp.asarray(micro, jnp.uint32)
    return jnp.stack([jnp.uint32(base_seed) ^ (s * jnp.uint32(0x9E3779B9)),
                      s + m * jnp.uint32(0x85EBCA6B)])


def make_train_step(cfg, scheme: str, *, optimizer: str = "adamw",
                    base_lr: float = 3e-4, total_steps: int = 1000,
                    schedule: str = "cosine", weight_decay: float = 0.1,
                    grad_clip: float = 1.0, base_seed: int = 0,
                    microbatches: int = 1, aux_weight: float = 0.01,
                    grad_transform=None):
    """Returns (init_state_fn, train_step_fn).

    grad_transform(grads, seed) -> grads: hook for DP gradient compression
    (dist.compression) or any custom reduction; applied before clipping.
    """
    opt_mod = {"adamw": adamw, "muon": muon}[optimizer]
    sched = schedules.get(schedule)

    def init_state(params) -> TrainState:
        return TrainState(params, opt_mod.init(params), jnp.zeros((), jnp.int32))

    def loss_fn(params, batch, seed):
        return lm.lm_loss(params, cfg, batch, scheme, seed, aux_weight=aux_weight)

    def train_step(state: TrainState, batch) -> tuple[TrainState, dict]:
        if microbatches == 1:
            seed = step_seed(base_seed, state.step, 0)
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch, seed)
        else:
            def micro(i):
                mb = jax.tree.map(
                    lambda x: x.reshape(microbatches, -1, *x.shape[1:])[i], batch)
                seed = step_seed(base_seed, state.step, i)
                return jax.value_and_grad(loss_fn)(state.params, mb, seed)

            def acc(carry, i):
                l, g = micro(i)
                cl, cg = carry
                return (cl + l, jax.tree.map(jnp.add, cg, g)), None

            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                state.params)
            (loss, grads), _ = jax.lax.scan(
                acc, (jnp.zeros(()), zero), jnp.arange(microbatches))
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)

        if grad_transform is not None:
            grads = grad_transform(grads, step_seed(base_seed ^ 0x5555, state.step))

        with jax.named_scope(SC.OPTIMIZER):
            grads, gnorm = adamw.clip_by_global_norm(grads, grad_clip)
            lr = sched(state.step, base_lr=base_lr, total_steps=total_steps)
            new_params, new_opt = opt_mod.update(
                grads, state.opt, state.params, lr=lr,
                weight_decay=weight_decay)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return init_state, train_step
