"""Pallas TPU fused selective scan (Mamba/S6 recurrence).

The XLA mamba path must materialize the discretized state tensors
``dA = exp(dt*A)`` and ``dBx = dt*B*x`` of shape (B, S, I, N) — an
``N``-fold (16x) memory amplification over the (B, S, I) activations that
makes hymba the worst roofline-fraction train cell (EXPERIMENTS.md
§Roofline summary). The CUDA selective-scan kernel keeps those tensors in
SRAM; this kernel is the TPU-native equivalent: everything lives in VMEM.

Grid ``(B, I/bi)``: each program owns one sequence row and a slice of the
inner dimension. The recurrence

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t
    y_t = h_t . C_t

runs as a ``fori_loop`` over time with the state (bi, N) in VMEM scratch;
dt/x stream in as (S, bi) blocks and B/C as (S, N) blocks. HBM traffic is
exactly the useful bytes: read dt, x (S*I), B, C (S*N), A (I*N); write y
(S*I). The (B, S, I, N) tensors never exist.

Interpret-mode validated against ``selective_scan_ref``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def selective_scan_ref(
    dt: jax.Array,     # (B, S, I) post-softplus step sizes
    x: jax.Array,      # (B, S, I) conv+silu activations
    bmat: jax.Array,   # (B, S, N)
    cmat: jax.Array,   # (B, S, N)
    a: jax.Array,      # (I, N) negative state matrix
    h0: jax.Array,     # (B, I, N)
) -> Tuple[jax.Array, jax.Array]:
    """Oracle: explicit (B, S, I, N) construction + sequential scan."""
    dA = jnp.exp(dt[..., None].astype(jnp.float32) * a.astype(jnp.float32))
    dBx = (dt * x)[..., None].astype(jnp.float32) * bmat[:, :, None, :].astype(
        jnp.float32
    )

    def step(h, inp):
        da_t, dbx_t, c_t = inp
        h = da_t * h + dbx_t
        y = jnp.einsum("bin,bn->bi", h, c_t)
        return h, y

    h_final, ys = jax.lax.scan(
        step,
        h0.astype(jnp.float32),
        (
            dA.swapaxes(0, 1),
            dBx.swapaxes(0, 1),
            cmat.swapaxes(0, 1).astype(jnp.float32),
        ),
    )
    return ys.swapaxes(0, 1).astype(x.dtype), h_final


def _scan_kernel(
    dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref,
    y_ref, hout_ref,
    h_scratch,
    *, seq_len: int,
):
    h_scratch[...] = h0_ref[0].astype(jnp.float32)      # (bi, N)
    a = a_ref[...].astype(jnp.float32)                  # (bi, N)

    def step(t, _):
        dt_t = dt_ref[0, t].astype(jnp.float32)         # (bi,)
        x_t = x_ref[0, t].astype(jnp.float32)
        b_t = b_ref[0, t].astype(jnp.float32)           # (N,)
        c_t = c_ref[0, t].astype(jnp.float32)
        dA = jnp.exp(dt_t[:, None] * a)                 # (bi, N)
        h = dA * h_scratch[...] + (dt_t * x_t)[:, None] * b_t[None, :]
        h_scratch[...] = h
        y_ref[0, t] = (h @ c_t).astype(y_ref.dtype)     # (bi,)
        return 0

    jax.lax.fori_loop(0, seq_len, step, 0)
    hout_ref[0] = h_scratch[...]


@functools.partial(jax.jit, static_argnames=("bi", "interpret"))
def selective_scan(
    dt: jax.Array,     # (B, S, I)
    x: jax.Array,      # (B, S, I)
    bmat: jax.Array,   # (B, S, N)
    cmat: jax.Array,   # (B, S, N)
    a: jax.Array,      # (I, N)
    h0: jax.Array,     # (B, I, N)
    *,
    bi: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    b, s, i = dt.shape
    n = a.shape[-1]
    bi = min(bi, i)
    if i % bi:
        raise ValueError(f"inner dim {i} must divide block {bi}")
    grid = (b, i // bi)

    y, h_final = pl.pallas_call(
        functools.partial(_scan_kernel, seq_len=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s, bi), lambda ib, ii: (ib, 0, ii)),
            pl.BlockSpec((1, s, bi), lambda ib, ii: (ib, 0, ii)),
            pl.BlockSpec((1, s, n), lambda ib, ii: (ib, 0, 0)),
            pl.BlockSpec((1, s, n), lambda ib, ii: (ib, 0, 0)),
            pl.BlockSpec((bi, n), lambda ib, ii: (ii, 0)),
            pl.BlockSpec((1, bi, n), lambda ib, ii: (ib, ii, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, s, bi), lambda ib, ii: (ib, 0, ii)),
            pl.BlockSpec((1, bi, n), lambda ib, ii: (ib, ii, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, i), x.dtype),
            jax.ShapeDtypeStruct((b, i, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bi, n), jnp.float32)],
        name="selective_scan",
        interpret=interpret,
    )(dt, x, bmat, cmat, a, h0)
    return y, h_final
