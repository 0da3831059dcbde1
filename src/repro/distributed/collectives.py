"""Distributed-optimization collectives (shard_map building blocks).

``compressed_psum_grads`` — gradient all-reduce over the DP axes with int8
quantization: each shard quantizes its local gradient (per-leaf symmetric
scale), all-reduces the int8 payload in int32 accumulation space, and
all-reduces the scales; the dequantized result approximates the exact psum
with 4x less wire traffic (2x vs bf16). Used by ``train.py --compress-dp``
and accounted in the roofline's collective term via
``training.compression.compressed_bytes``.

Error feedback lives OUTSIDE the collective (``training.compression``):
the residual between the exact local grad and its quantized form is
carried on-host per worker.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _quantize(g: jax.Array) -> Tuple[jax.Array, jax.Array]:
    amax = jnp.max(jnp.abs(g)).astype(jnp.float32)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def psum_compressed(tree: Any, axis_name) -> Any:
    """int8-compressed psum; call INSIDE shard_map.

    A shared quantization grid is required for exactness of the sum: the
    scale is the GLOBAL amax (scalar pmax — negligible wire cost), every
    shard quantizes against it, payloads accumulate in int32, and a single
    dequant recovers the sum. Per-shard error <= scale/2, so the summed
    error is bounded by n_shards * scale / 2 (tight and unbiased-ish; the
    error-feedback wrapper in ``training.compression`` absorbs the rest).
    """

    def one(g):
        amax = jax.lax.pmax(jnp.max(jnp.abs(g)).astype(jnp.float32), axis_name)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(
            jnp.round(g.astype(jnp.float32) / scale), -127, 127
        ).astype(jnp.int8)
        acc = jax.lax.psum(q.astype(jnp.int32), axis_name)
        return (acc.astype(jnp.float32) * scale).astype(g.dtype)

    return jax.tree_util.tree_map(one, tree)


def make_dp_allreduce(mesh: Mesh, *, compress: bool = False, axes=("data",)):
    """Returns grads -> grads averaged over the DP axes, via shard_map.

    Gradient leaves are expected replicated over the DP axes already under
    GSPMD; this explicit variant exists for the compressed path where the
    wire format matters (int8), which GSPMD cannot express.
    """
    axis_names = tuple(a for a in axes if a in mesh.shape)

    def allreduce(grads):
        if not axis_names:
            return grads

        spec = P()  # replicated per-shard view

        @partial(
            shard_map, mesh=mesh,
            in_specs=jax.tree_util.tree_map(lambda _: spec, grads),
            out_specs=jax.tree_util.tree_map(lambda _: spec, grads),
        )
        def body(g):
            n = 1
            for a in axis_names:
                n *= mesh.shape[a]
            if compress:
                summed = g
                for a in axis_names:
                    summed = psum_compressed(summed, a)
                return jax.tree_util.tree_map(lambda x: x / n, summed)
            return jax.tree_util.tree_map(
                lambda x: jax.lax.psum(x, axis_names) / n, g
            )

        return body(grads)

    return allreduce
