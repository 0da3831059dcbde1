"""Pallas TPU paged decode attention — block-table-indirected KV streaming.

The dense decode kernel (``kernels.decode_attention``) streams one
contiguous ``(S, Hkv, hd)`` cache row per sequence. Under paging there is
no contiguous row: a trajectory's KV lives in fixed-size blocks scattered
across a pool shared by every slot on the replica, addressed through a
per-sequence **block table** (``repro.rollout.kv_allocator``).

The indirection moves into the BlockSpec index map: block tables (and the
per-sequence scalars) are scalar-prefetched, and grid step ``(b, j)`` DMAs
pool block ``tables[b, j]`` into VMEM — logical position ``j*bs + i`` of
sequence ``b``. Everything else is the dense kernel's online softmax:

* grid ``(B, nb)`` with the table dimension innermost; the query block (a
  single token, all H heads) stays resident across the sweep;
* blocks past the valid length are skipped (``pl.when``), so compute and
  (post-prefetch) bandwidth scale with the trajectory's *actual* length —
  the whole point of charging admission by allocated blocks;
* GQA queries are reshaped to (Hkv, rep, hd) against un-repeated KV.

The fused ``paged_decode_attention_update`` variant also writes the new
token's K/V row in place: the output pool block index comes from the
scalar-prefetched write position, the caches alias their outputs, and the
new token's attention contribution is folded in analytically on the last
grid step — only the single touched block ever moves back to HBM.

``paged_prefill_attention`` generalizes the decode sweep to multi-token
query blocks: Q rows are a trajectory's *suffix* tokens (absolute offset
scalar-prefetched per row) while K/V still stream block-by-block from the
pool — the suffix-prefill path shared-prefix forks use to skip re-running
the resident prompt.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(
    tables_ref,               # SMEM (B, nb) block tables (prefetched)
    lens_ref,                 # SMEM (B,) valid lengths (prefetched)
    q_ref,                    # (1, H, hd)
    k_ref, v_ref,             # (1, bs, Hkv, hd) — pool block tables[b, j]
    o_ref,                    # (1, H, hd)
    acc_ref, m_ref, l_ref,    # VMEM scratch (H, hd), (H, 1), (H, 1)
    *, bs: int, nb: int, rep: int, scale: float,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = lens_ref[pl.program_id(0)]
    k_lo = j * bs

    @pl.when(k_lo < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)             # (H, hd)
        k = k_ref[0].astype(jnp.float32)             # (bs, Hkv, hd)
        v = v_ref[0].astype(jnp.float32)
        h, hd = q.shape
        hkv = k.shape[1]
        qg = q.reshape(hkv, rep, hd)
        s = jax.lax.dot_general(
            qg, k.transpose(1, 2, 0),               # (Hkv, hd, bs)
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                    # (Hkv, rep, bs)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos < length, s, NEG_INF)

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

    @pl.when(j == nb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(
    q: jax.Array,             # (B, H, hd)
    k_pool: jax.Array,        # (N, bs, Hkv, hd)
    v_pool: jax.Array,        # (N, bs, Hkv, hd)
    block_tables: jax.Array,  # (B, nb) int32
    lengths: jax.Array,       # (B,) int32 valid positions
    *,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over a block-paged KV pool. Returns (B, H, hd)."""
    b, h, hd = q.shape
    n, bs, hkv, _ = k_pool.shape
    nb = block_tables.shape[1]
    rep = h // hkv
    scale = 1.0 / math.sqrt(hd)

    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, bs=bs, nb=nb, rep=rep, scale=scale
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec((1, h, hd), lambda ib, j, tb, ln: (ib, 0, 0)),
                pl.BlockSpec(
                    (1, bs, hkv, hd),
                    lambda ib, j, tb, ln: (tb[ib, j], 0, 0, 0),
                ),
                pl.BlockSpec(
                    (1, bs, hkv, hd),
                    lambda ib, j, tb, ln: (tb[ib, j], 0, 0, 0),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, h, hd), lambda ib, j, tb, ln: (ib, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((h, hd), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        name="paged_decode_attention",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q,
      k_pool, v_pool)
    return out


def _paged_prefill_kernel(
    tables_ref,               # SMEM (B, nb) block tables (prefetched)
    meta_ref,                 # SMEM (2, B): row 0 = q_offset, row 1 = length
    q_ref,                    # (1, S, H, hd) suffix queries (right-padded)
    k_ref, v_ref,             # (1, bs, Hkv, hd) — pool block tables[b, j]
    o_ref,                    # (1, S, H, hd)
    acc_ref, m_ref, l_ref,    # VMEM scratch (Hkv, S*rep, hd), (Hkv, S*rep, 1) x2
    *, bs: int, nb: int, rep: int, scale: float,
):
    ib = pl.program_id(0)
    j = pl.program_id(1)
    q_off = meta_ref[0, ib]
    length = meta_ref[1, ib]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_lo = j * bs

    @pl.when(k_lo < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)             # (S, H, hd)
        k = k_ref[0].astype(jnp.float32)             # (bs, Hkv, hd)
        v = v_ref[0].astype(jnp.float32)
        sq, h, hd = q.shape
        hkv = k.shape[1]
        # group-major rows: row s*rep + r of group g is query (s, g*rep + r)
        qg = (
            q.reshape(sq, hkv, rep, hd)
            .transpose(1, 0, 2, 3)
            .reshape(hkv, sq * rep, hd)
        )
        s = jax.lax.dot_general(
            qg, k.transpose(1, 2, 0),               # (Hkv, hd, bs)
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                    # (Hkv, S*rep, bs)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // rep
        # causal over the combined prefix+suffix window; padded query rows
        # (qpos >= length) keep l == 0 and finalize to zeros
        s = jnp.where((kpos <= qpos) & (kpos < length), s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        out = jax.lax.dot_general(
            p, v.transpose(1, 0, 2), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                            # (Hkv, S*rep, hd)
        acc_ref[...] = acc_ref[...] * alpha + out
        m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        sq = q_ref.shape[1]
        hkv, _, hd = acc_ref.shape
        l = jnp.maximum(l_ref[...], 1e-30)
        o = (acc_ref[...] / l).reshape(hkv, sq, rep, hd)
        o_ref[0] = (
            o.transpose(1, 0, 2, 3).reshape(sq, hkv * rep, hd)
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention(
    q: jax.Array,             # (B, S, H, hd) suffix queries (right-padded)
    k_pool: jax.Array,        # (N, bs, Hkv, hd)
    v_pool: jax.Array,        # (N, bs, Hkv, hd)
    block_tables: jax.Array,  # (B, nb) int32
    q_offsets: jax.Array,     # (B,) int32 absolute position of q[:, 0]
    lengths: jax.Array,       # (B,) int32 total valid positions
    *,
    interpret: bool = False,
) -> jax.Array:
    """Suffix-prefill attention over a block-paged KV pool.

    Queries are a trajectory's suffix tokens (absolute positions
    ``q_offsets[b] + i``); K/V stream from the pool via the scalar-
    prefetched block table — the resident shared prefix plus the suffix
    rows the caller scattered in beforehand. Causal over prefix+suffix.
    Returns (B, S, H, hd); padded query rows come back zero.
    """
    b, sq, h, hd = q.shape
    n, bs, hkv, _ = k_pool.shape
    nb = block_tables.shape[1]
    rep = h // hkv
    scale = 1.0 / math.sqrt(hd)
    meta = jnp.stack([q_offsets.astype(jnp.int32), lengths.astype(jnp.int32)])

    out = pl.pallas_call(
        functools.partial(
            _paged_prefill_kernel, bs=bs, nb=nb, rep=rep, scale=scale
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec(
                    (1, sq, h, hd), lambda ib, j, tb, mt: (ib, 0, 0, 0)
                ),
                pl.BlockSpec(
                    (1, bs, hkv, hd),
                    lambda ib, j, tb, mt: (tb[ib, j], 0, 0, 0),
                ),
                pl.BlockSpec(
                    (1, bs, hkv, hd),
                    lambda ib, j, tb, mt: (tb[ib, j], 0, 0, 0),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, sq, h, hd), lambda ib, j, tb, mt: (ib, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((hkv, sq * rep, hd), jnp.float32),
                pltpu.VMEM((hkv, sq * rep, 1), jnp.float32),
                pltpu.VMEM((hkv, sq * rep, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, sq, h, hd), q.dtype),
        name="paged_prefill_attention",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), meta, q, k_pool, v_pool)
    return out


def _paged_update_kernel(
    tables_ref,               # SMEM (B, nb)
    meta_ref,                 # SMEM (2, B): row 0 = write_pos, row 1 = length
    q_ref, k_ref, v_ref,      # (1, H, hd), (1, bs, Hkv, hd) x2
    kn_ref, vn_ref,           # (1, Hkv, hd) new row
    o_ref, ko_ref, vo_ref,    # out + aliased pool blocks
    acc_ref, m_ref, l_ref,
    *, bs: int, nb: int, rep: int, scale: float,
):
    ib = pl.program_id(0)
    j = pl.program_id(1)
    wp = meta_ref[0, ib]
    length = meta_ref[1, ib]
    wp_blk = wp // bs

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_lo = j * bs

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
        # the write slot holds garbage (not yet written); exclude it from
        # the stream — the NEW token's contribution lands analytically below
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

    # in-place block write: copy the matching input block once, overwrite
    # the single row — only this block moves (input_output_aliasing)
    @pl.when(j == wp_blk)
    def _write_row():
        row = wp % bs
        ko_ref[0] = k_ref[0]
        vo_ref[0] = v_ref[0]
        ko_ref[0, row] = kn_ref[0].astype(ko_ref.dtype)
        vo_ref[0, row] = vn_ref[0].astype(vo_ref.dtype)

    @pl.when(j == nb - 1)
    def _finalize():
        q = q_ref[0].astype(jnp.float32)
        h, hd = q.shape
        kn = kn_ref[0].astype(jnp.float32)
        vn = vn_ref[0].astype(jnp.float32)
        hkv = kn.shape[0]
        qg = q.reshape(hkv, rep, hd)
        s_new = jnp.sum(qg * kn[:, None, :], axis=-1).reshape(h, 1) * scale
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_fin = jnp.maximum(m_prev, s_new)
        p_new = jnp.exp(s_new - m_fin)
        alpha = jnp.exp(m_prev - m_fin)
        l_fin = alpha * l_prev + p_new
        vrep = jnp.broadcast_to(vn[:, None, :], (hkv, rep, hd)).reshape(h, hd)
        acc_fin = acc_ref[...] * alpha + p_new * vrep
        o_ref[0] = (acc_fin / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("interpret",), donate_argnums=(1, 2)
)
def paged_decode_attention_update(
    q: jax.Array,             # (B, H, hd)
    k_pool: jax.Array,        # (N, bs, Hkv, hd) — donated, updated in place
    v_pool: jax.Array,        # (N, bs, Hkv, hd) — donated, updated in place
    k_new: jax.Array,         # (B, Hkv, hd)
    v_new: jax.Array,         # (B, Hkv, hd)
    block_tables: jax.Array,  # (B, nb) int32
    write_pos: jax.Array,     # (B,) int32 logical position of the new token
    *,
    interpret: bool = False,
):
    """Fused paged decode attention + in-place pool block row write.

    ``write_pos`` is the new token's logical position; the valid attention
    length is ``write_pos + 1`` (the new token attends to itself via the
    analytic fold-in). Returns (out (B, H, hd), k_pool', v_pool')."""
    b, h, hd = q.shape
    n, bs, hkv, _ = k_pool.shape
    nb = block_tables.shape[1]
    rep = h // hkv
    scale = 1.0 / math.sqrt(hd)
    meta = jnp.stack(
        [write_pos.astype(jnp.int32), write_pos.astype(jnp.int32) + 1]
    )

    out, new_k, new_v = pl.pallas_call(
        functools.partial(
            _paged_update_kernel, bs=bs, nb=nb, rep=rep, scale=scale
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec((1, h, hd), lambda ib, j, tb, mt: (ib, 0, 0)),
                pl.BlockSpec(
                    (1, bs, hkv, hd),
                    lambda ib, j, tb, mt: (tb[ib, j], 0, 0, 0),
                ),
                pl.BlockSpec(
                    (1, bs, hkv, hd),
                    lambda ib, j, tb, mt: (tb[ib, j], 0, 0, 0),
                ),
                pl.BlockSpec((1, hkv, hd), lambda ib, j, tb, mt: (ib, 0, 0)),
                pl.BlockSpec((1, hkv, hd), lambda ib, j, tb, mt: (ib, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, h, hd), lambda ib, j, tb, mt: (ib, 0, 0)),
                pl.BlockSpec(
                    (1, bs, hkv, hd),
                    lambda ib, j, tb, mt: (tb[ib, mt[0, ib] // bs], 0, 0, 0),
                ),
                pl.BlockSpec(
                    (1, bs, hkv, hd),
                    lambda ib, j, tb, mt: (tb[ib, mt[0, ib] // bs], 0, 0, 0),
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
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # operand order: (tables, meta, q, k_pool, v_pool, k_new, v_new)
        input_output_aliases={3: 1, 4: 2},
        name="paged_decode_attention_update",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), meta, q, k_pool, v_pool, k_new, v_new)
    return out, new_k, new_v
