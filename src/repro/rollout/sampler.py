"""Token sampling for the rollout engine.

Two layers:

* ``sample`` / ``sample_rows`` — logits -> (token, behavior logprob), one
  key per row.
* **Per-trajectory key streams** (``stream_key`` / ``stream_keys``): the
  key for a trajectory's ``p``-th sampled token is
  ``fold_in(fold_in(base_key, traj_id), p)`` — a pure function of
  ``(seed, traj_id, position)``. Stochastic decode is therefore invariant
  under batch composition (slot compaction), instance placement, and
  interrupt/migrate re-prefill: wherever and with whomever a trajectory is
  batched, token ``p`` draws from the same key. (The seed engine instead
  split one engine-global key per step across the whole batch, so a
  trajectory's tokens depended on its slot index and on every admission
  that ever advanced the engine key.)
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def sample(
    logits: jax.Array,          # (B, V)
    key: jax.Array,
    *,
    temperature: float = 1.0,
    top_k: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (tokens (B,), behavior logprobs (B,)).

    Behavior logprobs are ALWAYS from the untempered distribution the policy
    gradient targets (log softmax of raw logits at the sampled token) — the
    temperature only shapes exploration, matching standard RLHF practice.
    """
    lp_raw = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    if temperature <= 0.0:
        tokens = jnp.argmax(logits, axis=-1)
    else:
        scaled = logits.astype(jnp.float32) / temperature
        if top_k > 0:
            kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
            scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
        tokens = jax.random.categorical(key, scaled, axis=-1)
    blp = jnp.take_along_axis(lp_raw, tokens[:, None], axis=-1)[:, 0]
    return tokens.astype(jnp.int32), blp


def sample_rows(
    logits: jax.Array,          # (B, V)
    keys: jax.Array,            # (B, 2) one PRNG key per row
    *,
    temperature: float = 1.0,
    top_k: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Row-wise ``sample`` with an independent key per row.

    Each row's draw is a function of its own key only, so the result for a
    given (logits row, key) pair is identical no matter which other rows
    share the batch — the property per-slot key streams rely on.
    """
    toks, blps = jax.vmap(
        lambda lg, k: sample(lg[None], k, temperature=temperature, top_k=top_k)
    )(logits, keys)
    return toks[:, 0], blps[:, 0]


# --------------------------------------------------- per-trajectory streams
def stream_key(
    base_key: jax.Array, traj_id: int, position: int
) -> jax.Array:
    """Key for trajectory ``traj_id``'s ``position``-th sampled token."""
    return _fold2(base_key, jnp.uint32(traj_id), jnp.uint32(position))


@jax.jit
def stream_keys(
    base_key: jax.Array,
    traj_ids: jax.Array,        # (B,)
    positions: jax.Array,       # (B,)
) -> jax.Array:
    """Batched ``stream_key``: (B, 2) keys, one per (trajectory, position).
    One program, ``jit_stream_keys`` in a device trace."""
    return jax.vmap(lambda i, p: _fold2(base_key, i, p))(traj_ids, positions)


@jax.jit
def _fold2(base_key, traj_id, position):
    return jax.random.fold_in(jax.random.fold_in(base_key, traj_id), position)
