"""Pallas TPU fused DAPO loss (token-level clipped PG objective).

Training consumes batches of up to ``batch * group * seq`` token logprobs;
the loss is elementwise (ratio, clip, min) followed by a masked global
reduction. Unfused, XLA materializes several (B, T) f32 temporaries in HBM;
the kernel fuses the elementwise chain with a two-stage reduction — each
grid cell reduces its (bb, bt) tile to partial sums in VMEM and the final
(n_bb, n_bt) partials are summed outside (tiny).

Each partial sum is written broadcast over one ``(8, 128)`` output tile:
Mosaic stores only tiles aligned to the vreg layout, and refuses the
``(1, 1)`` scalar blocks a naive per-cell output would need. The tiles cost
4 KiB per grid cell; the wrapper reads element ``[0, 0]`` of each.

Outputs three partial-sum planes: clipped objective, ratio (a staleness
diagnostic: mean importance weight of the consumed batch), and mask count.

Interpret-mode validated against ``ref.dapo_loss_ref``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# one vreg-aligned output tile per grid cell (holds a broadcast scalar)
_TILE = (8, 128)


def _dapo_kernel(
    lp_ref, olp_ref, adv_ref, mask_ref,
    obj_ref, ratio_ref, cnt_ref,
    *, eps_low: float, eps_high: float,
):
    lp = lp_ref[...].astype(jnp.float32)
    olp = olp_ref[...].astype(jnp.float32)
    adv = adv_ref[...].astype(jnp.float32)          # (bb, 1)
    m = mask_ref[...].astype(jnp.float32)
    ratio = jnp.exp(lp - olp)
    clipped = jnp.clip(ratio, 1.0 - eps_low, 1.0 + eps_high)
    obj = jnp.minimum(ratio * adv, clipped * adv)
    for ref, val in ((obj_ref, obj * m), (ratio_ref, ratio * m), (cnt_ref, m)):
        total = jnp.sum(val, axis=(0, 1), keepdims=True)    # (1, 1)
        ref[0, 0] = jnp.broadcast_to(total, ref.shape[2:])


@functools.partial(
    jax.jit, static_argnames=("eps_low", "eps_high", "bb", "bt", "interpret")
)
def dapo_loss(
    logprobs: jax.Array,       # (B, T)
    old_logprobs: jax.Array,   # (B, T)
    advantages: jax.Array,     # (B,)
    mask: jax.Array,           # (B, T)
    *,
    eps_low: float = 0.2,
    eps_high: float = 0.28,
    bb: int = 8,
    bt: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    b, t = logprobs.shape
    bb, bt = min(bb, b), min(bt, t)
    if b % bb or t % bt:
        raise ValueError(f"shape ({b},{t}) must divide blocks ({bb},{bt})")
    grid = (b // bb, t // bt)
    adv2d = advantages.reshape(b, 1)

    partial_shape = jax.ShapeDtypeStruct(grid + _TILE, jnp.float32)
    partial_spec = pl.BlockSpec((1, 1) + _TILE, lambda ib, it: (ib, it, 0, 0))
    obj_p, ratio_p, cnt_p = pl.pallas_call(
        functools.partial(_dapo_kernel, eps_low=eps_low, eps_high=eps_high),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bt), lambda ib, it: (ib, it)),
            pl.BlockSpec((bb, bt), lambda ib, it: (ib, it)),
            pl.BlockSpec((bb, 1), lambda ib, it: (ib, 0)),
            pl.BlockSpec((bb, bt), lambda ib, it: (ib, it)),
        ],
        out_specs=[partial_spec, partial_spec, partial_spec],
        out_shape=[partial_shape, partial_shape, partial_shape],
        name="dapo_loss",
        interpret=interpret,
    )(logprobs, old_logprobs, adv2d, mask)

    obj_p, ratio_p, cnt_p = (x[:, :, 0, 0] for x in (obj_p, ratio_p, cnt_p))
    denom = jnp.maximum(cnt_p.sum(), 1.0)
    loss = -obj_p.sum() / denom
    mean_ratio = ratio_p.sum() / denom
    return loss, mean_ratio
