"""Multi-device sharded rollout backend: one instance = a pod, not a chip.

StaleFlow's rollout "instances" are resource pools behind data servers
(PAPER.md §4) — a single replica of the serving engine can span many
accelerators, the way Laminar/AsyncFlow deploy multi-GPU rollout replicas.
``ShardedBackend`` makes that real for this engine: it is the paged
``RolloutInstance`` with its data plane laid out SPMD over a 1-D
``("tensor",)`` mesh (``repro.launch.mesh.make_rollout_mesh``):

* **params** — *stored* column-sharded where output dimensions split
  cleanly (attention heads on wq/wk/wv, SwiGLU hidden on w_gate/w_up,
  vocab on lm_head; specs from
  ``repro.distributed.sharding.rollout_param_spec``) and gathered
  replicated just-in-time inside each jitted step
  (``ctx.gather_params``, ZeRO-3 style): per-device parameter HBM
  shrinks, while every matmul still runs full-width — a column-sharded
  matmul is not bitwise-stable against its full-width counterpart (XLA
  picks micro-kernels per output width), and bitwise is the contract
  here.
* **paged K/V pool** — sharded on its KV-head axis
  (``paged_pool_spec``): every device holds the full block structure but
  only ``Hkv / shard_count`` heads per block. Block tables, the
  refcounted allocator, CoW prefix sharing, and preemption stay host-side
  and *unchanged* — sharding is invisible to the control plane.
* **compute** — prefill/decode run through ``ShardedPrefillRunner`` /
  ``ShardedPagedDecodeRunner``, which enter ``ctx.rollout_sharding`` so
  the traced model gathers activations to replicated form before any
  contraction would cross a sharded dimension (``ctx.gather``).

Equality contract: no reduction is ever partitioned — attention is
per-head, softmax runs over the (unsharded) sequence axis, and every
matmul contracts over a replicated dimension — so greedy decode matches
the single-device paged engine, tokens exactly and behavior logprobs to
float32 rounding (the partitioned program may group sums differently),
across batched admission, CoW prefix sharing, and preemption.
``tests/test_sharded_backend.py`` pins this on 8 forced host devices,
``chip_smoke.py --four-chips`` on four TPU chips.

Memory plane: ``kv_budget`` and every reported byte figure are
*per-device* — the engine charges ``k5 / shard_count`` per token, and
``snapshot().kv_cache`` matches what ``SimBackend``/``CostModel`` compute
at the same ``shard_count``, so the coordinator balances pods and chips
with one consistent HBM picture.

The runners force ``impl="ref"`` through the kernels dispatch: the
jnp reference paths are pure XLA and partition automatically under
GSPMD, while the Pallas TPU kernels would need an explicit shard_map
wrapping (future work — on CPU CI this is the default path anyway).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import ctx
from repro.distributed.sharding import (
    ROLLOUT_AXIS,
    paged_cache_shardings,
    paged_pool_spec,
    rollout_params_shardings,
    validate_rollout_shards,
)
from repro.rollout.engine import RolloutInstance
from repro.rollout.runners import PagedDecodeRunner, PrefillRunner


class ShardedPrefillRunner(PrefillRunner):
    """``PrefillRunner`` traced under the rollout tensor-parallel context.

    The prompt forward itself is replicated work (its inputs are host
    token ids and column-sharded weights — the ``ctx.gather`` boundaries
    keep activations replicated between projections); the paged re-block
    scatter and the CoW tail copy land on the head-sharded pool, pinned
    by ``pool_sharding`` so each device writes only its head slice.
    """

    def __init__(self, *args: Any, mesh: Mesh, **kw: Any):
        super().__init__(*args, impl="ref", **kw)
        self.mesh = mesh

    def run(self, params, cache, jobs):
        with ctx.rollout_sharding(self.mesh):
            return super().run(params, cache, jobs)


class ShardedPagedDecodeRunner(PagedDecodeRunner):
    """``PagedDecodeRunner`` traced under the rollout tensor-parallel
    context: per-shard paged attention over the head-sharded pool (block
    tables replicate to every device), head outputs gathered at the
    ``wo`` boundary, K/V writes pinned to the pool layout."""

    def __init__(self, *args: Any, mesh: Mesh, **kw: Any):
        super().__init__(*args, impl="ref", **kw)
        self.mesh = mesh

    def run(self, params, cache, active, block_tables, last_tokens, keys):
        with ctx.rollout_sharding(self.mesh):
            return super().run(
                params, cache, active, block_tables, last_tokens, keys
            )


def _check_mesh(mesh: Mesh, shard_count: int) -> None:
    if ROLLOUT_AXIS not in mesh.shape:
        raise ValueError(
            f"rollout mesh must carry a {ROLLOUT_AXIS!r} axis, got "
            f"{dict(mesh.shape)}"
        )
    if mesh.shape[ROLLOUT_AXIS] != shard_count:
        raise ValueError(
            f"mesh {ROLLOUT_AXIS!r} axis has {mesh.shape[ROLLOUT_AXIS]} "
            f"devices but shard_count is {shard_count}"
        )


class ShardedBackend(RolloutInstance):
    """A paged ``RolloutInstance`` spanning ``shard_count`` devices.

    Drop-in ``EngineBackend``: the coordinator command stream, admission
    policy, group prefix sharing, and preemption semantics are inherited
    unchanged — only array placement and the runner data plane differ.
    ``kv_budget`` is **per device**; pass ``mesh`` to colocate several
    instances on one device set, otherwise a fresh
    ``make_rollout_mesh(shard_count)`` over the first ``shard_count``
    local devices is built.
    """

    def __init__(
        self,
        inst_id: int,
        cfg: Any,
        params: Any,
        version: int,
        *,
        shard_count: int,
        mesh: Optional[Mesh] = None,
        paged: bool = True,
        **kw: Any,
    ):
        if not paged:
            raise ValueError(
                "ShardedBackend shards the paged K/V pool; paged=False has "
                "no pool to shard (use the 'jax' backend instead)"
            )
        validate_rollout_shards(
            shard_count, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads
        )
        if mesh is None:
            from repro.launch.mesh import make_rollout_mesh

            mesh = make_rollout_mesh(shard_count)
        _check_mesh(mesh, shard_count)
        self.mesh = mesh
        super().__init__(
            inst_id,
            cfg,
            params,
            version,
            paged=True,
            shard_count=shard_count,
            **kw,
        )
        self._replicated = NamedSharding(mesh, P())
        self.params = self._place_params(params)
        cache_sh = paged_cache_shardings(mesh, self.cache)
        self.cache = jax.device_put(self.cache, cache_sh)
        self._last_tokens = jax.device_put(self._last_tokens, self._replicated)

    # ----------------------------------------------------- runner factories
    # called from RolloutInstance.__init__ (self.mesh and self.cache are
    # already set): one construction site, sharded variants swapped in
    def _pool_sharding(self) -> NamedSharding:
        return NamedSharding(
            self.mesh, paged_pool_spec(self.mesh, self.cache["k"].shape)
        )

    def _make_prefill_runner(self, cfg: Any, **kw: Any) -> ShardedPrefillRunner:
        return ShardedPrefillRunner(
            cfg, mesh=self.mesh, pool_sharding=self._pool_sharding(), **kw
        )

    def _make_paged_decode_runner(
        self, cfg: Any, **kw: Any
    ) -> ShardedPagedDecodeRunner:
        return ShardedPagedDecodeRunner(
            cfg, mesh=self.mesh, pool_sharding=self._pool_sharding(), **kw
        )

    # ------------------------------------------------------------ placement
    def _place_params(self, params: Any) -> Any:
        return jax.device_put(params, rollout_params_shardings(self.mesh, params))

    def pull(self, params: Any, version: int, now: float = 0.0) -> None:
        """Adopt a new parameter version, re-sharding it onto the pod
        (the PS publishes host/replicated trees)."""
        super().pull(self._place_params(params), version, now)

    # ------------------------------------------------------------- geometry
    def shard_sizes(self) -> Sequence[Tuple[int, ...]]:
        """Per-device K-pool shard shapes — test/debug introspection."""
        return [s.data.shape for s in self.cache["k"].addressable_shards]
