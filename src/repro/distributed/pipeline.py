"""GPipe-style pipeline parallelism over a "stage" mesh axis.

Not used by the default production mesh (DP x TP saturates 256 chips for
the assigned model sizes); provided as the scale-out lever beyond ~10^3
chips, where a third axis keeps TP groups intra-pod and DCN hops become
pipeline edges (DESIGN.md §3).

``gpipe_apply`` runs a stage-sharded stack of layers over M microbatches
with the classic (M + S - 1)-tick schedule inside ONE shard_map:

  tick t:  stage 0 ingests microbatch t (while t < M);
           every stage applies its layers to its current buffer;
           activations hop stage s -> s+1 via collective_permute;
           stage S-1 emits microbatch t-(S-1) (while t >= S-1).

Bubble fraction = (S-1)/(M+S-1), the GPipe bound. Activations are the only
cross-stage traffic (one (mb, ...) tensor per tick per edge).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def gpipe_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,            # leaves with leading dim = n_stages
    x_microbatches: jax.Array,    # (M, mb, ...) microbatched inputs
    *,
    mesh: Mesh,
    stage_axis: str = "stage",
) -> jax.Array:
    """Returns (M, mb, ...) outputs of the full stage stack."""
    n_stages = mesh.shape[stage_axis]
    m = x_microbatches.shape[0]
    ticks = m + n_stages - 1

    params_specs = jax.tree_util.tree_map(lambda _: P(stage_axis), stage_params)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(params_specs, P()),
        out_specs=P(),
    )
    def run(sp_local, xs):
        sp = jax.tree_util.tree_map(lambda a: a[0], sp_local)  # drop stage dim
        sid = jax.lax.axis_index(stage_axis)
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(t, carry):
            buf, outs = carry
            mb_in = jnp.clip(t, 0, m - 1)
            inp = jnp.where(sid == 0, xs[mb_in], buf)
            y = stage_fn(sp, inp)
            # stage S-1 emits microbatch t-(S-1); other stages contribute 0
            mb_out = jnp.clip(t - (n_stages - 1), 0, m - 1)
            emit = jnp.logical_and(sid == n_stages - 1, t >= n_stages - 1)
            outs = outs.at[mb_out].add(
                jnp.where(emit, y, jnp.zeros_like(y))
            )
            buf = jax.lax.ppermute(y, stage_axis, perm)
            return buf, outs

        # initial carries must be marked stage-varying for shard_map typing
        buf0 = jax.lax.pcast(jnp.zeros_like(xs[0]), stage_axis, to="varying")
        outs0 = jax.lax.pcast(jnp.zeros_like(xs), stage_axis, to="varying")
        _, outs = jax.lax.fori_loop(0, ticks, tick, (buf0, outs0))
        # outputs live on the last stage only; sum across stages replicates
        return jax.lax.psum(outs, stage_axis)

    return run(stage_params, x_microbatches)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
