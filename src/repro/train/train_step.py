"""Training step: grad accumulation over microbatches (scan), AdamW update,
remat-friendly.  Designed so the AOT-lowered HLO stays compact (microbatch
loop is a while; layer stack is a while inside it)."""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.optim.adamw import AdamWConfig, AdamWState, adamw_init, adamw_update, cosine_schedule


def pick_microbatches(cfg, shape, ddp: int, budget_bytes: float = 4e9) -> int:
    """Smallest power-of-two microbatch count keeping per-device live
    activations (layer-boundary residuals under unit-remat) under budget."""
    b_dev = max(1, shape.global_batch // ddp)
    resid = b_dev * shape.seq_len * cfg.d_model * 2 * max(1, cfg.n_layers)
    m = 1
    while m < b_dev and resid / m > budget_bytes:
        m *= 2
    # microbatch count must divide the global batch AND keep >= ddp per mb
    while m > 1 and (shape.global_batch % m or shape.global_batch // m < ddp):
        m //= 2
    return max(1, m)


def make_train_step(model, opt_cfg: AdamWConfig, microbatches: int = 1,
                    total_steps: int = 100000, warmup: int = 500,
                    grad_shardings=None):
    """-> train_step(params, opt_state, batch) -> (params, opt_state, metrics).

    ``grad_shardings`` (a params-like tree of NamedShardings) pins each
    microbatch's gradients to the parameter sharding, so GSPMD emits
    reduce-scatters into the shards instead of full-tensor all-reduces."""

    def loss_fn(params, mb):
        return model.loss_fn(params, mb)

    def _pin(g):
        if grad_shardings is None:
            return g
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, g, grad_shardings)

    def train_step(params, opt_state: AdamWState, batch):
        if microbatches == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            grads = _pin(grads)
        else:
            def split(a):
                return a.reshape((microbatches, a.shape[0] // microbatches)
                                 + a.shape[1:])

            mbs = jax.tree_util.tree_map(split, batch)

            def body(carry, mb):
                gacc, lacc = carry
                loss, g = jax.value_and_grad(loss_fn)(params, mb)
                g = _pin(g)
                gacc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(a.dtype), gacc, g)
                return (gacc, lacc + loss), None

            init = (jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params),
                jnp.float32(0.0))
            (grads, loss), _ = jax.lax.scan(body, init, mbs)
            grads = jax.tree_util.tree_map(lambda g: g / microbatches, grads)
            loss = loss / microbatches

        lr_scale = cosine_schedule(opt_state.step, warmup=warmup,
                                   total=total_steps)
        params, opt_state, metrics = adamw_update(
            grads, opt_state, params, opt_cfg, lr_scale=lr_scale)
        metrics["loss"] = loss
        metrics["lr_scale"] = lr_scale
        return params, opt_state, metrics

    return train_step


def make_eval_step(model):
    def eval_step(params, batch):
        return model.loss_fn(params, batch)
    return eval_step
