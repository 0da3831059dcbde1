"""Training-step factories.

``make_rl_train_step`` — the system's real step: DAPO/GRPO objective over a
consumed staleness-buffer batch (tokens + behavior logprobs + advantages +
response mask), grads, clip, AdamW. This is also what the multi-pod dry-run
lowers for every ``train_4k`` cell.

``make_lm_train_step`` — plain next-token cross-entropy (used by ablations
and as a pretraining-style baseline).

Both support gradient rematerialization (``remat=True`` checkpoints each
scanned block) and return (params, opt_state, metrics).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import model as M
from repro.rl import losses
from repro.training.optimizer import AdamWConfig, adamw_update

# Kernel implementation the trainer differentiates through: XLA's fused jnp
# attention and loss, because the Pallas kernels have no backward pass.
TRAINER_IMPL = "ref"


def make_rl_train_step(
    cfg: ArchConfig,
    opt_cfg: AdamWConfig = AdamWConfig(),
    *,
    objective: str = "dapo",
    aux_coef: float = 0.01,
    remat: bool = False,
    impl: Optional[str] = None,
    accum_steps: int = 1,
) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    batch:
      tokens            (B, T) int32 — prompt + response, right padded
      behavior_logprobs (B, T) f32   — 0 outside response positions
      advantages        (B,)   f32
      mask              (B, T) f32   — 1 on response positions (shifted to
                                       align with next-token prediction)
      [frontend_embeds  (B, ...)     — vlm/audio stubs]

    ``accum_steps > 1`` splits the batch into microbatches scanned with
    f32 gradient accumulation — activation temp memory drops ~linearly
    (the lever that fits 76B/132B-class training under the 16 GB HBM gate;
    see EXPERIMENTS.md §Perf).
    """
    obj_fn = losses.dapo_objective if objective == "dapo" else losses.grpo_objective

    def loss_fn(params, batch):
        logits, aux = M.forward(
            cfg, params, batch["tokens"],
            frontend_embeds=batch.get("frontend_embeds"),
            impl=impl, remat=remat,
        )
        # next-token alignment: logits[:, t] predicts tokens[:, t+1]
        lp = losses.token_logprobs(
            logits[:, :-1], batch["tokens"][:, 1:]
        )                                           # (B, T-1)
        blp = batch["behavior_logprobs"][:, 1:]
        mask = batch["mask"][:, 1:]
        loss, metrics = obj_fn(lp, blp, batch["advantages"], mask, impl=impl) \
            if objective == "dapo" else obj_fn(lp, blp, batch["advantages"], mask)
        total = loss + aux_coef * aux["moe_aux"]
        metrics = dict(metrics)
        metrics["pg_loss"] = loss
        metrics["moe_aux"] = aux["moe_aux"]
        return total, metrics

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
        else:
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps)
                                    + x.shape[1:]),
                batch,
            )
            grads0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )

            def body(acc, mb):
                g_acc, loss_acc, metric_acc = acc
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb
                )
                g_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g
                )
                metric_acc = {
                    k: metric_acc[k] + jnp.asarray(v, jnp.float32)
                    for k, v in m.items()
                }
                return (g_acc, loss_acc + l, metric_acc), None

            metrics0 = {
                k: jnp.zeros((), jnp.float32)
                for k in ("mean_is_ratio", "pg_loss", "moe_aux")
            }
            (grads, loss, msum), _ = jax.lax.scan(
                body, (grads0, jnp.zeros((), jnp.float32), metrics0), micro
            )
            grads = jax.tree_util.tree_map(lambda g: g / accum_steps, grads)
            loss = loss / accum_steps
            metrics = {k: v / accum_steps for k, v in msum.items()}
        params, opt_state, opt_metrics = adamw_update(
            params, grads, opt_state, opt_cfg
        )
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


def make_lm_train_step(
    cfg: ArchConfig,
    opt_cfg: AdamWConfig = AdamWConfig(),
    *,
    remat: bool = False,
    impl: Optional[str] = None,
) -> Callable:
    def loss_fn(params, batch):
        logits, aux = M.forward(
            cfg, params, batch["tokens"],
            frontend_embeds=batch.get("frontend_embeds"),
            impl=impl, remat=remat,
        )
        lp = losses.token_logprobs(logits[:, :-1], batch["tokens"][:, 1:])
        mask = batch.get("mask")
        mask = mask[:, 1:] if mask is not None else jnp.ones_like(lp)
        nll = -(lp * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        return nll + 0.01 * aux["moe_aux"], {"nll": nll}

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch
        )
        params, opt_state, opt_metrics = adamw_update(
            params, grads, opt_state, opt_cfg
        )
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step
