"""Pallas TPU grouped matmul (MoE expert FFN building block).

MoE dispatch produces per-expert token blocks ``x: (E, C, D)``; each expert
applies its own weights ``w: (E, D, F)``. The kernel is a classic blocked
matmul with the expert index as the outermost grid dimension and the
contraction (D) dimension innermost, accumulating into the output block in
VMEM (initialized on the first D step):

    grid = (E, C/bc, F/bf, D/bd)
    x block (bc, bd) . w block (bd, bf) -> out block (bc, bf), f32 acc

Tiles are MXU-aligned (multiples of 128 where the dims allow). The SwiGLU
composition (gate/up/down) lives in ``ops.moe_ffn_pallas``: three gmm calls
with the silu fusion left to XLA — the matmuls dominate.

Interpret-mode validated against ``ref.moe_gmm_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gmm_kernel(x_ref, w_ref, o_ref, *, n_d_blocks: int):
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0]                                    # (bc, bd)
    w = w_ref[0]                                    # (bd, bf)
    o_ref[0] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("bc", "bf", "bd", "interpret"))
def grouped_matmul(
    x: jax.Array,            # (E, C, D)
    w: jax.Array,            # (E, D, F)
    *,
    bc: int = 128,
    bf: int = 128,
    bd: int = 512,
    interpret: bool = False,
) -> jax.Array:
    e, c, d = x.shape
    f = w.shape[-1]
    bc, bf, bd = min(bc, c), min(bf, f), min(bd, d)
    if c % bc or f % bf or d % bd:
        raise ValueError(f"dims ({c},{f},{d}) must divide blocks ({bc},{bf},{bd})")
    grid = (e, c // bc, f // bf, d // bd)

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, n_d_blocks=d // bd),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda ie, ic, if_, id_: (ie, ic, id_)),
            pl.BlockSpec((1, bd, bf), lambda ie, ic, if_, id_: (ie, id_, if_)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda ie, ic, if_, id_: (ie, ic, if_)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), jnp.float32),
        scratch_shapes=[],
        name="grouped_matmul",
        interpret=interpret,
    )(x, w)
    return out


def moe_expert_ffn(
    x: jax.Array,            # (E, C, D) dispatched tokens
    w_gate: jax.Array,       # (E, D, F)
    w_up: jax.Array,         # (E, D, F)
    w_down: jax.Array,       # (E, F, D)
    *,
    interpret: bool = False,
) -> jax.Array:
    """SwiGLU expert FFN via three grouped matmuls (kernel composition)."""
    g = grouped_matmul(x, w_gate, interpret=interpret)
    u = grouped_matmul(x, w_up, interpret=interpret)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return grouped_matmul(h, w_down, interpret=interpret).astype(x.dtype)
