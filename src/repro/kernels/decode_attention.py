"""Pallas TPU decode attention — the rollout hot spot.

The paper's Table 3 attributes 89.9% of rollout step time to per-token
decode; on TPU this op is HBM-bandwidth bound (it streams the whole KV
cache per step), so the kernel's job is to move KV through VMEM in large
aligned blocks with no repeated GQA materialization.

One new token per sequence attends to a (B, S, Hkv, hd) cache:
grid ``(B, S/bk)`` with the cache dimension innermost; the query block
(all H heads of one sequence — a single token) stays resident in VMEM
across the whole sweep while K/V stream through. Online softmax scratch
(acc/m/l) is carried per-sequence and the output is written on the final
cache block. Ring-cache validity is handled with a per-sequence length
(SMEM scalar): positions ``>= length`` are masked.

GQA: the query is reshaped to (Hkv, rep, hd) so scores are computed
directly against un-repeated KV — ``rep``x less VMEM traffic than
repeat-then-MHA.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    len_ref,                  # SMEM (1,) valid length for this sequence
    q_ref,                    # (1, H, hd)
    k_ref, v_ref,             # (1, bk, Hkv, hd)
    o_ref,                    # (1, H, hd)
    acc_ref, m_ref, l_ref,    # VMEM scratch (H, hd), (H, 1), (H, 1)
    *, bk: int, n_blocks: int, rep: int, scale: float,
):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[pl.program_id(0)]
    k_lo = ik * bk

    @pl.when(k_lo < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)             # (H, hd)
        k = k_ref[0].astype(jnp.float32)             # (bk, Hkv, hd)
        v = v_ref[0].astype(jnp.float32)             # (bk, Hkv, hd)
        h, hd = q.shape
        hkv = k.shape[1]
        qg = q.reshape(hkv, rep, hd)
        # scores: (Hkv, rep, bk) = qg . k^T over hd, batched over Hkv
        s = jax.lax.dot_general(
            qg, k.transpose(1, 2, 0),               # (Hkv, hd, bk)
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos < length, s, NEG_INF)

        sh = s.reshape(h, -1)                        # (H, bk)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(sh, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(sh - m_new)                      # (H, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pg = p.reshape(hkv, rep, -1)                 # (Hkv, rep, bk)
        out = jax.lax.dot_general(
            pg, v.transpose(1, 0, 2),               # (Hkv, bk, hd)
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                            # (Hkv, rep, hd)
        acc_ref[...] = acc_ref[...] * alpha + out.reshape(h, hd)
        m_ref[...] = m_new

    @pl.when(ik == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_update_kernel(
    scalars_ref,              # SMEM (2, B): row 0 = write_pos, row 1 = lengths
    q_ref, k_ref, v_ref,      # (1, H, hd), (1, bk, Hkv, hd) x2
    kn_ref, vn_ref,           # (1, Hkv, hd) new row
    o_ref, ko_ref, vo_ref,    # (1, H, hd), (1, bk, Hkv, hd) x2 (aliased caches)
    acc_ref, m_ref, l_ref,
    *, bk: int, n_blocks: int, rep: int, scale: float,
):
    ib = pl.program_id(0)
    ik = pl.program_id(1)
    wp = scalars_ref[0, ib]
    length = scalars_ref[1, ib]
    wp_blk = wp // bk

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_lo = ik * bk

    @pl.when(k_lo < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        h, hd = q.shape
        hkv = k.shape[1]
        qg = q.reshape(hkv, rep, hd)
        s = jax.lax.dot_general(
            qg, k.transpose(1, 2, 0), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        # exclude the slot being overwritten (ring eviction) — its NEW
        # contribution is added analytically on the last step
        s = jnp.where((kpos < length) & (kpos != wp), s, NEG_INF)
        sh = s.reshape(h, -1)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sh, axis=-1, keepdims=True))
        p = jnp.exp(sh - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pg = p.reshape(hkv, rep, -1)
        out = jax.lax.dot_general(
            pg, v.transpose(1, 0, 2), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + out.reshape(h, hd)
        m_ref[...] = m_new

    # in-place row write: fill the aliased output block once (from the
    # matching input block) and overwrite the single row — the rest of the
    # cache never moves (input_output_aliasing)
    @pl.when(ik == wp_blk)
    def _write_row():
        blk_k = k_ref[0]
        blk_v = v_ref[0]
        row = wp % bk
        ko_ref[0] = blk_k
        vo_ref[0] = blk_v
        ko_ref[0, row] = kn_ref[0].astype(ko_ref.dtype)
        vo_ref[0, row] = vn_ref[0].astype(vo_ref.dtype)

    @pl.when(ik == n_blocks - 1)
    def _finalize():
        # analytic contribution of the NEW token (not yet in the cache)
        q = q_ref[0].astype(jnp.float32)
        h, hd = q.shape
        kn = kn_ref[0].astype(jnp.float32)       # (Hkv, hd)
        vn = vn_ref[0].astype(jnp.float32)
        hkv = kn.shape[0]
        qg = q.reshape(hkv, rep, hd)
        s_new = jnp.sum(qg * kn[:, None, :], axis=-1).reshape(h, 1) * scale
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_fin = jnp.maximum(m_prev, s_new)
        p_new = jnp.exp(s_new - m_fin)           # (H, 1)
        alpha = jnp.exp(m_prev - m_fin)
        l_fin = alpha * l_prev + p_new
        vrep = jnp.broadcast_to(
            vn[:, None, :], (hkv, rep, hd)
        ).reshape(h, hd)
        acc_fin = acc_ref[...] * alpha + p_new * vrep
        o_ref[0] = (acc_fin / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"), donate_argnums=(1, 2))
def decode_attention_update(
    q: jax.Array,            # (B, H, hd)
    k_cache: jax.Array,      # (B, S, Hkv, hd) — donated, updated in place
    v_cache: jax.Array,      # (B, S, Hkv, hd) — donated, updated in place
    k_new: jax.Array,        # (B, Hkv, hd) this step's key
    v_new: jax.Array,        # (B, Hkv, hd) this step's value
    write_pos: jax.Array,    # (B,) int32 ring slot to overwrite
    lengths: jax.Array,      # (B,) int32 valid entries INCLUDING the new one
    *,
    bk: int = 512,
    interpret: bool = False,
):
    """Fused decode attention + in-place ring-cache row write.

    The XLA-graph decode path must rewrite the full cache per layer (the
    one-hot select of EXPERIMENTS.md §Perf A1); this kernel streams the
    cache through VMEM once, writes back ONLY the touched block (the
    caches alias their outputs), and folds the new token's attention
    contribution in analytically — the useful-byte floor of the decode
    roofline. Returns (out (B, H, hd), k_cache', v_cache')."""
    b, h, hd = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    rep = h // hkv
    bk = min(bk, s)
    if s % bk:
        raise ValueError(f"cache length {s} must divide block {bk}")
    n_blocks = s // bk
    scale = 1.0 / math.sqrt(hd)
    scalars = jnp.stack(
        [write_pos.astype(jnp.int32), lengths.astype(jnp.int32)]
    )

    grid = (b, n_blocks)
    # scalar-prefetched write positions drive the OUTPUT cache block index:
    # only the touched block is ever written back (in-place via aliasing)
    out, new_k, new_v = pl.pallas_call(
        functools.partial(
            _decode_update_kernel, bk=bk, n_blocks=n_blocks, rep=rep,
            scale=scale,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, h, hd), lambda ib, ik, sc: (ib, 0, 0)),
                pl.BlockSpec((1, bk, hkv, hd), lambda ib, ik, sc: (ib, ik, 0, 0)),
                pl.BlockSpec((1, bk, hkv, hd), lambda ib, ik, sc: (ib, ik, 0, 0)),
                pl.BlockSpec((1, hkv, hd), lambda ib, ik, sc: (ib, 0, 0)),
                pl.BlockSpec((1, hkv, hd), lambda ib, ik, sc: (ib, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, h, hd), lambda ib, ik, sc: (ib, 0, 0)),
                pl.BlockSpec(
                    (1, bk, hkv, hd),
                    lambda ib, ik, sc: (ib, sc[0, ib] // bk, 0, 0),
                ),
                pl.BlockSpec(
                    (1, bk, hkv, hd),
                    lambda ib, ik, sc: (ib, sc[0, ib] // bk, 0, 0),
                ),
            ],
            scratch_shapes=[
                pltpu.VMEM((h, hd), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, hd), q.dtype),
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        ],
        input_output_aliases={2: 1, 3: 2},  # k_cache->new_k, v_cache->new_v
        name="decode_attention_update",
        interpret=interpret,
    )(scalars, q, k_cache, v_cache, k_new, v_new)
    return out, new_k, new_v


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(
    q: jax.Array,            # (B, H, hd)
    k_cache: jax.Array,      # (B, S, Hkv, hd)
    v_cache: jax.Array,      # (B, S, Hkv, hd)
    lengths: jax.Array,      # (B,) int32
    *,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, h, hd = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    rep = h // hkv
    bk = min(bk, s)
    if s % bk:
        raise ValueError(f"cache length {s} must divide block {bk}")
    n_blocks = s // bk
    scale = 1.0 / math.sqrt(hd)

    grid = (b, n_blocks)
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, bk=bk, n_blocks=n_blocks, rep=rep, scale=scale
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, h, hd), lambda ib, ik: (ib, 0, 0)),
                pl.BlockSpec((1, bk, hkv, hd), lambda ib, ik: (ib, ik, 0, 0)),
                pl.BlockSpec((1, bk, hkv, hd), lambda ib, ik: (ib, ik, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, h, hd), lambda ib, ik: (ib, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, hd), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        name="decode_attention",
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k_cache, v_cache)
    return out
