"""Pallas TPU flash attention (training / prefill forward).

Online-softmax attention tiled for VMEM: grid ``(B*H, Sq/bq, Skv/bk)`` with
the KV dimension innermost; running max/denominator/accumulator live in
VMEM scratch and the output block is finalized on the last KV step. GQA is
folded into the K/V index maps (query head ``h`` reads KV head ``h / rep``),
so no repeated KV materialization. Causal and sliding-window masks skip
fully-masked KV blocks via ``pl.when`` (the block is scheduled but no MXU
work is issued).

Block sizes default to MXU-aligned 128x128 tiles in the (Sq, Skv) plane;
``hd`` stays whole (the MXU contracts over it). VMEM footprint per step:
``bq*hd + 2*bk*hd + bq*bk`` f32 words plus scratch — well under 16 MiB for
hd <= 256.

TPU is the target; CPU validation runs interpret mode against
``ref.flash_attention_ref`` (tests sweep shapes/dtypes).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, bq: int, bk: int, n_kv_blocks: int, causal: bool, window: int,
    q_offset: int, scale: float,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions of this block's queries/keys
    q_lo = iq * bq + q_offset
    k_lo = ik * bk

    # visibility pre-check: skip blocks that are fully masked
    diag_ok = (not causal) or (k_lo <= q_lo + bq - 1)
    win_ok = (window <= 0) or (k_lo + bk - 1 > q_lo - window)
    # (conditions are on traced ints when q_offset is traced; both paths jit)

    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0].astype(jnp.float32)            # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                    # (bq, bk)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                          # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)   # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)              # (bq, 1)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    if isinstance(diag_ok, bool) and isinstance(win_ok, bool):
        if diag_ok and win_ok:
            _compute()
    else:
        pl.when(jnp.logical_and(diag_ok, win_ok))(_compute)

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "bq", "bk", "interpret"),
)
def flash_attention(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, Skv, Hkv, hd)
    v: jax.Array,            # (B, Skv, Hkv, hd)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    bq = min(bq, sq)
    bk = min(bk, skv)
    if sq % bq or skv % bk:
        raise ValueError(f"seq lengths ({sq},{skv}) must divide blocks ({bq},{bk})")
    scale = 1.0 / math.sqrt(hd)

    # (B, S, H, hd) -> (B*H, S, hd) head-major layout for clean 2D tiles
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, hd)

    n_kv_blocks = skv // bk
    grid = (b * h, sq // bq, n_kv_blocks)

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        # query head bh = bi*H + hi reads KV head bi*Hkv + hi//rep
        bi = bh // h
        hi = bh % h
        return (bi * hkv + hi // rep, ik, 0)

    out = pl.pallas_call(
        functools.partial(
            _fa_kernel,
            bq=bq, bk=bk, n_kv_blocks=n_kv_blocks,
            causal=causal, window=window, q_offset=q_offset, scale=scale,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), q_map),
            pl.BlockSpec((1, bk, hd), kv_map),
            pl.BlockSpec((1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
