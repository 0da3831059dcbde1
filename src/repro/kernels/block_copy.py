"""Pallas TPU pool-block copy — the device side of copy-on-write.

Prefix sharing (``repro.rollout.prefix_cache``) maps a group prompt's full
KV blocks read-only into every member's block table, but the partially
filled tail block must be duplicated per member so decode appends never
alias. The duplication is a pure HBM->HBM block move inside the K/V pools
(``(layers, n_blocks, bs, Hkv, hd)``); materializing it in XLA as
``pool.at[:, dst].set(pool[:, src])`` round-trips the *entire* pool through
a gather/scatter pair. This kernel moves only the touched blocks:

* ``src``/``dst`` block indices are scalar-prefetched; grid step
  ``(c, layer)`` DMAs pool block ``src[c]`` of one layer into VMEM and
  writes it back at ``dst[c]`` — both K and V in the same step;
* the pools alias their outputs (``input_output_aliases``), so untouched
  blocks never move — per copy, exactly ``2 * bs * Hkv * hd`` elements of
  HBM traffic per layer, independent of pool size.

Callers pad the copy list to a bucketed length with ``dst = NULL_BLOCK``
(the pool's garbage sink): padded steps write garbage into a block nothing
reads unmasked, keeping compiled shapes stable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy_kernel(src_ref, dst_ref, ki_ref, vi_ref, ko_ref, vo_ref):
    del src_ref, dst_ref  # consumed by the BlockSpec index maps
    ko_ref[...] = ki_ref[...]
    vo_ref[...] = vi_ref[...]


@functools.partial(
    jax.jit, static_argnames=("interpret",), donate_argnums=(0, 1)
)
def copy_pool_blocks(
    k_pool: jax.Array,        # (L, N, bs, Hkv, hd) — aliased, updated in place
    v_pool: jax.Array,        # (L, N, bs, Hkv, hd) — aliased, updated in place
    src: jax.Array,           # (C,) int32 source block per copy
    dst: jax.Array,           # (C,) int32 destination block per copy
    *,
    interpret: bool = False,
):
    """Copy pool blocks ``src[c] -> dst[c]`` in both K and V pools.

    Returns ``(k_pool', v_pool')``. Destinations must be distinct (the
    rollout allocator hands out fresh tail blocks, so they are); a padded
    entry may target the null block.
    """
    l, n, bs, hkv, hd = k_pool.shape
    c = src.shape[0]

    blk = pl.BlockSpec(
        (1, 1, bs, hkv, hd), lambda ic, il, s, d: (il, s[ic], 0, 0, 0)
    )
    out_blk = pl.BlockSpec(
        (1, 1, bs, hkv, hd), lambda ic, il, s, d: (il, d[ic], 0, 0, 0)
    )
    new_k, new_v = pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(c, l),
            in_specs=[blk, blk],
            out_specs=[out_blk, out_blk],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # operand order: (src, dst, k_pool, v_pool)
        input_output_aliases={2: 0, 3: 1},
        name="copy_pool_blocks",
        interpret=interpret,
    )(src.astype(jnp.int32), dst.astype(jnp.int32), k_pool, v_pool)
    return new_k, new_v
