"""Prefill / decode runners: the batched data plane of a rollout instance.

Production engines (vLLM, SGLang, TensorRT-LLM) split the generation loop
into two phases with very different batching economics:

* **Prefill** is compute-bound and benefits from batching whole prompts —
  ``PrefillRunner`` admits *all* eligible waiting trajectories in one padded
  forward pass per length bucket and writes the resulting row caches into
  the instance's batch cache with a single jitted scatter (replacing the
  seed engine's per-trajectory ``init_cache(cfg, 1, ...)`` forward +
  tensor-by-tensor ``tree_map(.at[].set)`` loop).
* **Decode** is memory/parameter-bound and pays for every batch row whether
  or not a trajectory occupies it — ``DecodeRunner`` gathers only the
  *active* slots into a power-of-two compaction bucket, decodes that, and
  scatters the updated rows back, instead of always decoding ``max_slots``
  rows.

Equivalence contract (tested in ``tests/test_engine_equivalence.py``): on
the CPU/TPU XLA backends both runners are **bitwise** equivalent per row to
the seed single-row path — batched matmul rows do not interact (MoE expert
capacity is the one documented exception: capacity is a function of batch
size, so compaction can change token dropping at capacity limits; the
runtime's reduced configs are dense). Sampling keys are per-trajectory
*stream keys* (``repro.rollout.sampler.stream_keys``): token ``p`` of
trajectory ``t`` always draws from ``fold_in(fold_in(base, t), p)``, so
both greedy AND stochastic decoding are bit-for-bit invariant under slot
compaction, batch composition, and migration.

Both runners are pure data-plane helpers: they know nothing about the
waiting queue, KV budget, or the coordination protocol — that policy stays
in ``RolloutInstance`` (``repro.rollout.engine``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.distributed.ctx import gather_params
from repro.models import model as M
from repro.obs.spans import span
from repro.rollout.sampler import sample_rows

Cache = Dict[str, Any]

# batch-axis index per cache entry (gather/scatter targets)
BATCH_AXIS = {
    "pos": 0, "k": 1, "v": 1, "conv": 1, "ssm": 1, "xk": 1, "xv": 1,
    "mlstm": 2, "slstm": 1,
}


def named_jit(name: str, fn: Callable, **jit_kw) -> Callable:
    """``jax.jit(fn, **jit_kw)`` under a fixed name: the program lowers as
    ``jit_<name>``, the name its module carries in a device trace (and
    that the benchmark's trace readers match), however ``fn`` was built."""

    @functools.wraps(fn)
    def program(*a, **kw):
        return fn(*a, **kw)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **jit_kw)


def round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def pad_keys(keys: jax.Array, rows: int) -> jax.Array:
    """Pad a (n, 2) per-slot key batch to ``rows`` rows by repeating the
    first key (pad rows' draws are never read)."""
    n = keys.shape[0]
    if n >= rows:
        return keys
    return jnp.concatenate(
        [keys, jnp.broadcast_to(keys[:1], (rows - n, keys.shape[1]))]
    )


def scatter_keys(keys: jax.Array, active: Sequence[int], rows: int) -> jax.Array:
    """Place per-active-slot keys at their slot rows of a (rows, 2) key
    batch (inactive rows repeat the first key; their draws are masked)."""
    full = jnp.broadcast_to(keys[:1], (rows, keys.shape[1]))
    return full.at[jnp.asarray(list(active), jnp.int32)].set(keys)


def _row_index(name: str, rows: jax.Array) -> Tuple:
    return (slice(None),) * BATCH_AXIS[name] + (rows,)


def gather_rows(cache: Cache, rows: jax.Array) -> Cache:
    """Extract batch rows ``rows`` of every cache entry (compact view)."""
    return {
        name: jax.tree_util.tree_map(lambda f: f[_row_index(name, rows)], val)
        for name, val in cache.items()
    }


def scatter_rows(cache: Cache, row_cache: Cache, rows: jax.Array) -> Cache:
    """Write batch rows of ``row_cache`` into ``cache`` at indices ``rows``.

    ``row_cache`` leaves must carry exactly ``len(rows)`` entries on their
    batch axis. One fused scatter over the whole cache pytree.
    """
    out = {}
    for name, full in cache.items():
        idx = _row_index(name, rows)
        out[name] = jax.tree_util.tree_map(
            lambda f, r: f.at[idx].set(r.astype(f.dtype)), full, row_cache[name]
        )
    return out


@dataclass
class PrefillJob:
    """One planned admission: trajectory tokens destined for a cache slot.

    Group admission (prefix sharing, paged mode only): ``extra_slots`` /
    ``extra_keys`` name additional group members that decode off this job's
    prompt. The prompt is prefilled **once**; its full blocks (already
    mapped into every member's table by the allocator) are written once via
    ``blocks``, the per-slot small state is scattered to every member slot,
    and the partially-filled tail block — the only prompt block decode will
    ever write — is device-copied from ``tail_src`` into each member's
    private ``tail_dsts`` block (eager copy-on-write). Each member samples
    its own first token from the shared last-position logits with its own
    key, in admission order.
    """

    slot: int
    tokens: List[int]          # prompt + partial response (re-prefill)
    key: jax.Array             # per-trajectory stream key (sampler.stream_key)
    blocks: Optional[List[int]] = None  # paged mode: the slot's block table
    # --- group admission (prefix sharing) ---
    extra_slots: List[int] = field(default_factory=list)
    extra_keys: List[jax.Array] = field(default_factory=list)
    tail_src: Optional[int] = None       # prefill-written partial tail block
    tail_dsts: List[int] = field(default_factory=list)  # one per extra member
    # --- suffix mode (shared-prefix fork, paged mode only) ---
    # ``suffix_start`` set => only tokens[suffix_start:] run through the
    # model; positions below it are already resident in the pool via the
    # leading shared entries of ``blocks`` (the donor's prefix blocks).
    # ``resident_tokens`` is the pool-resident position count — the write
    # boundary (== suffix_start except block-aligned forks, which re-read
    # the last resident position for its logits without re-writing it).
    suffix_start: Optional[int] = None
    resident_tokens: int = 0

    @property
    def bucket_len(self) -> int:
        return len(self.tokens)

    @property
    def forwarded(self) -> int:
        """Tokens this job runs through the model (its suffix in suffix
        mode): what it adds to ``PrefillResult.prefill_tokens``."""
        return len(self.tokens) - (self.suffix_start or 0)

    @property
    def n_members(self) -> int:
        return 1 + len(self.extra_slots)


@dataclass
class PrefillResult:
    """Per-member sampled continuations, aligned with the submitted jobs
    flattened member-wise (a job's primary member first, then its
    ``extra_slots`` in order; plain jobs contribute one entry).
    ``prefill_tokens`` counts tokens actually run through the model — a
    shared group prompt counts once and suffix jobs count only their
    suffix, which is the saving. ``tail_copies`` counts pool-block copies
    issued for eager CoW tails (zero under lazy CoW)."""

    tokens: List[int] = field(default_factory=list)
    logprobs: List[float] = field(default_factory=list)
    prefill_tokens: int = 0
    tail_copies: int = 0


class PrefillRunner:
    """Bucketed multi-row batched prefill + fused cache scatter.

    ``batch_limit`` caps rows per forward; ``batch_limit=1`` degenerates to
    the seed engine's single-row path exactly (same shapes, same calls, same
    key order), which is what the equivalence tests compare against.
    """

    def __init__(
        self,
        cfg: ArchConfig,
        *,
        max_len: int,
        prefill_bucket: int = 16,
        batch_limit: int = 0,            # 0 = unlimited (one pass per bucket)
        temperature: float = 1.0,
        frontend_fn: Optional[Callable[[int], jax.Array]] = None,
        paged_block_size: int = 0,       # 0 = dense slot-row scatter
        paged_null_block: int = 0,
        impl: Optional[str] = None,      # kernels.ops dispatch override
        pool_sharding: Optional[Any] = None,   # pin paged K/V layout (TP)
    ):
        self.cfg = cfg
        self.max_len = max_len
        self.prefill_bucket = prefill_bucket
        self.batch_limit = batch_limit
        self.temperature = temperature
        self.frontend_fn = frontend_fn
        self.paged_block_size = paged_block_size
        self.paged_null_block = paged_null_block
        self.impl = impl
        # NamedSharding for the (l, n_blocks, bs, hkv, hd) pools: the
        # sharded backend pins scatter/copy outputs so GSPMD can never
        # decide to replicate the pool (which would silently void the
        # per-device memory accounting)
        self.pool_sharding = pool_sharding
        # shard-stored params are gathered replicated inside the step
        # (ctx.gather_params: ZeRO-3-style JIT materialization, no-op on
        # single-device instances) so matmul widths never change
        self._jit_prefill = named_jit(
            "rollout_prefill",
            lambda params, *a, **kw: M.prefill(
                cfg, gather_params(params), *a, impl=impl, **kw
            ),
        )
        self._jit_scatter = named_jit("rollout_scatter", scatter_rows)
        self._jit_paged_scatter = named_jit(
            "rollout_paged_scatter", self._paged_scatter
        )
        # donate the cache: the copy is always fed a fresh intermediate (a
        # scatter output) and donating lets the inner Pallas aliasing move
        # only the touched blocks instead of round-tripping the whole pool
        self._jit_block_copy = named_jit(
            "rollout_block_copy", M.copy_kv_blocks,
            static_argnames=("impl",), donate_argnums=(0,),
        )
        # per-row sampling with per-trajectory stream keys: each member's
        # first token is a function of (its key, its logits row) only
        self._jit_sample = named_jit(
            "rollout_prefill_sample",
            lambda lg, ks: sample_rows(lg, ks, temperature=self.temperature),
        )
        # suffix-prefill dispatches, one per (suffix bucket, batch rows)
        self._suffix_steps: Dict[Tuple[int, int], Any] = {}
        # program spans also record here (a TrajectoryTracer; None: the
        # profiler's trace only)
        self.tracer = None

    def bucket_of(self, n_tokens: int) -> int:
        return min(round_up(max(n_tokens, 1), self.prefill_bucket), self.max_len)

    def _paged_scatter(self, cache, row_cache, slots, row_ids, flat_blocks):
        """Scatter a contiguous prefill row cache into the paged layout:
        per-slot entries land at their slot rows, K/V rows are re-blocked
        and written to the pool at the jobs' block tables (padding entries
        target the null block — a masked garbage sink).

        ``slots``/``row_ids`` are member-expanded: group admission writes
        one prefill row's small state (``pos``, hybrid/audio slot caches)
        to *every* member slot (``row_ids`` names each member's source
        row); plain waves pass the identity mapping."""
        small = {n: v for n, v in cache.items() if n not in ("k", "v")}
        rows = gather_rows(
            {n: v for n, v in row_cache.items() if n not in ("k", "v")},
            row_ids,
        )
        out = scatter_rows(small, rows, slots)
        l, r, s, hkv, hd = row_cache["k"].shape
        bs = cache["k"].shape[2]
        rk = row_cache["k"].reshape(l, r * (s // bs), bs, hkv, hd)
        rv = row_cache["v"].reshape(l, r * (s // bs), bs, hkv, hd)
        out["k"] = cache["k"].at[:, flat_blocks].set(rk.astype(cache["k"].dtype))
        out["v"] = cache["v"].at[:, flat_blocks].set(rv.astype(cache["v"].dtype))
        if self.pool_sharding is not None:
            out["k"] = jax.lax.with_sharding_constraint(
                out["k"], self.pool_sharding
            )
            out["v"] = jax.lax.with_sharding_constraint(
                out["v"], self.pool_sharding
            )
        return out

    def copy_blocks(
        self, cache: Cache, copies: Sequence[Tuple[int, int]]
    ) -> Cache:
        """Device-copy pool blocks ``src -> dst``, padded to a power-of-two
        copy count aimed at the null garbage block to bound compiled
        shapes. Used for eager CoW tails at admission and by the engine's
        lazy copy-at-first-divergence."""
        pad = next_pow2(len(copies)) - len(copies)
        src = [s for s, _ in copies] + [self.paged_null_block] * pad
        dst = [d for _, d in copies] + [self.paged_null_block] * pad
        return self._jit_block_copy(
            cache,
            jnp.asarray(src, jnp.int32),
            jnp.asarray(dst, jnp.int32),
            impl=self.impl,
        )

    def _suffix_step(self, bucket: int, n: int):
        """Jitted suffix prefill for ``n`` single-member fork jobs whose
        padded suffixes fit ``bucket`` tokens: gather the members' small
        state rows, run ``paged_prefill_step`` against the shared pools,
        scatter the advanced positions back."""
        fn = self._suffix_steps.get((bucket, n))
        if fn is None:
            def step(params, cache, rows, slots, tables, q_off, res, lens):
                view = {
                    "pos": cache["pos"][slots],
                    "k": cache["k"],
                    "v": cache["v"],
                }
                logits, new = M.paged_prefill_step(
                    self.cfg, gather_params(params), rows, view,
                    tables, q_off, res, lens, impl=self.impl,
                )
                out = {
                    nm: v for nm, v in cache.items() if nm not in ("k", "v")
                }
                out["pos"] = cache["pos"].at[slots].set(new["pos"])
                out["k"], out["v"] = new["k"], new["v"]
                if self.pool_sharding is not None:
                    out["k"] = jax.lax.with_sharding_constraint(
                        out["k"], self.pool_sharding
                    )
                    out["v"] = jax.lax.with_sharding_constraint(
                        out["v"], self.pool_sharding
                    )
                return logits, out

            fn = named_jit("rollout_suffix_prefill", step)
            self._suffix_steps[(bucket, n)] = fn
        return fn

    def _run_suffix(
        self, params: Any, cache: Cache, jobs: Sequence[PrefillJob],
        offsets: Dict[int, int], result: PrefillResult,
    ) -> Cache:
        """Admit suffix-mode fork jobs: forward only each job's suffix
        against its donor's resident prefix blocks. Jobs are bucketed by
        (padded suffix length, table width) — one dispatch per bucket.

        The table width is ``ceil(full-prompt bucket / block_size)``, NOT
        the pool-wide ``max_len // block_size``: the gathered attention
        window must reduce over exactly as many K/V rows as the regular
        prefill's flash attention does for the same prompt, or the float
        summation grouping (softmax denominator, probs@V contraction)
        differs at the ulp level and the fork is no longer bit-for-bit
        equal to the full-prefill path."""
        by_bucket: Dict[Tuple[int, int], List[PrefillJob]] = {}
        order: List[Tuple[int, int]] = []
        for job in jobs:
            sb = self.bucket_of(len(job.tokens) - job.suffix_start)
            nbw = -(-self.bucket_of(len(job.tokens)) // self.paged_block_size)
            key = (sb, nbw)
            if key not in by_bucket:
                by_bucket[key] = []
                order.append(key)
            by_bucket[key].append(job)
        for b, nb in order:
            group = by_bucket[(b, nb)]
            n = len(group)
            with span("prefill.dispatch", self.tracer, rows=n):
                rows = np.zeros((n, b), np.int32)
                q_off = np.zeros((n,), np.int32)
                res = np.zeros((n,), np.int32)
                lens = np.zeros((n,), np.int32)
                tables = np.full((n, nb), self.paged_null_block, np.int32)
                for r, job in enumerate(group):
                    sfx = job.tokens[job.suffix_start:]
                    rows[r, : len(sfx)] = sfx
                    q_off[r] = job.suffix_start
                    res[r] = job.resident_tokens
                    lens[r] = len(job.tokens)
                    tables[r, : len(job.blocks)] = job.blocks
                slots = jnp.asarray([job.slot for job in group], jnp.int32)
                logits, cache = self._suffix_step(b, n)(
                    params, cache, jnp.asarray(rows), slots,
                    jnp.asarray(tables), jnp.asarray(q_off),
                    jnp.asarray(res), jnp.asarray(lens),
                )
                keys = jnp.stack([job.key for job in group])
                toks, blps = self._jit_sample(logits, keys)
            with span("prefill.sync", self.tracer, rows=n):
                toks_np = np.asarray(toks)
                blps_np = np.asarray(blps)
            for r, job in enumerate(group):
                base = offsets[id(job)]
                result.tokens[base] = int(toks_np[r])
                result.logprobs[base] = float(blps_np[r])
                result.prefill_tokens += job.forwarded
        return cache

    def _groups(self, jobs: Sequence[PrefillJob]) -> List[List[PrefillJob]]:
        """Group jobs by padded bucket length, preserving admission order,
        splitting groups at ``batch_limit`` rows."""
        by_bucket: Dict[int, List[PrefillJob]] = {}
        order: List[int] = []
        for job in jobs:
            b = self.bucket_of(len(job.tokens))
            if b not in by_bucket:
                by_bucket[b] = []
                order.append(b)
            by_bucket[b].append(job)
        limit = self.batch_limit if self.batch_limit > 0 else len(jobs)
        groups: List[List[PrefillJob]] = []
        for b in order:
            g = by_bucket[b]
            groups.extend(g[i : i + limit] for i in range(0, len(g), limit))
        return groups

    def run(
        self, params: Any, cache: Cache, jobs: Sequence[PrefillJob]
    ) -> Tuple[Cache, PrefillResult]:
        """Prefill every job into its slot(s). Returns (cache, samples).

        The result lists are aligned with ``jobs`` flattened member-wise
        (not with the internal bucket grouping). Group jobs run their
        prompt through the model once; every member then samples its own
        first token from the shared logits row with its own key.
        """
        offsets: Dict[int, int] = {}
        total = 0
        for job in jobs:
            offsets[id(job)] = total
            total += job.n_members
        result = PrefillResult(tokens=[0] * total, logprobs=[0.0] * total)
        suffix_jobs = [j for j in jobs if j.suffix_start is not None]
        for job in suffix_jobs:
            if not self.paged_block_size or job.extra_slots:
                raise ValueError(
                    "suffix prefill requires the paged cache and "
                    "single-member jobs"
                )
        jobs = [j for j in jobs if j.suffix_start is None]
        copies: List[Tuple[int, int]] = []
        for group in self._groups(jobs):
            with span("prefill.dispatch", self.tracer, rows=len(group)):
                bucket = self.bucket_of(max(len(j.tokens) for j in group))
                rows = np.zeros((len(group), bucket), np.int32)
                lengths = np.zeros((len(group),), np.int32)
                for r, job in enumerate(group):
                    rows[r, : len(job.tokens)] = job.tokens
                    lengths[r] = len(job.tokens)
                fe = (
                    self.frontend_fn(len(group))
                    if self.frontend_fn is not None
                    else None
                )
                row_cache = M.init_cache(self.cfg, len(group), self.max_len)
                logits, row_cache = self._jit_prefill(
                    params,
                    jnp.asarray(rows),
                    jnp.asarray(lengths),
                    row_cache,
                    frontend_embeds=fe,
                )
                # member expansion: group jobs scatter one row's small state to
                # every member slot and sample per member off the shared row
                member_rows: List[int] = []
                member_slots: List[int] = []
                member_keys: List[jax.Array] = []
                for r, job in enumerate(group):
                    if job.extra_slots and not self.paged_block_size:
                        raise ValueError("group prefill requires the paged cache")
                    member_rows.extend([r] * job.n_members)
                    member_slots.append(job.slot)
                    member_slots.extend(job.extra_slots)
                    member_keys.append(job.key)
                    member_keys.extend(job.extra_keys)
                    if job.tail_src is not None:
                        copies.extend((job.tail_src, d) for d in job.tail_dsts)
                expanded = len(member_rows) != len(group)
                slots = jnp.asarray(member_slots, jnp.int32)
                if self.paged_block_size:
                    nb = self.max_len // self.paged_block_size
                    flat = np.full((len(group) * nb,), self.paged_null_block,
                                   np.int32)
                    for r, job in enumerate(group):
                        flat[r * nb : r * nb + len(job.blocks)] = job.blocks
                    cache = self._jit_paged_scatter(
                        cache, row_cache, slots,
                        jnp.asarray(member_rows, jnp.int32), jnp.asarray(flat),
                    )
                else:
                    cache = self._jit_scatter(cache, row_cache, slots)
                if expanded:
                    logits = logits[jnp.asarray(member_rows, jnp.int32)]
                keys = jnp.stack(member_keys)
                toks, blps = self._jit_sample(logits, keys)
            with span("prefill.sync", self.tracer, rows=len(group)):
                toks_np = np.asarray(toks)
                blps_np = np.asarray(blps)
            m = 0
            for job in group:
                base = offsets[id(job)]
                for i in range(job.n_members):
                    result.tokens[base + i] = int(toks_np[m])
                    result.logprobs[base + i] = float(blps_np[m])
                    m += 1
                result.prefill_tokens += job.forwarded
        if copies:
            # eager CoW: duplicate prefilled tail blocks into each member's
            # private block
            cache = self.copy_blocks(cache, copies)
            result.tail_copies = len(copies)
        if suffix_jobs:
            cache = self._run_suffix(params, cache, suffix_jobs, offsets, result)
        return cache, result


@dataclass
class DecodeResult:
    """One decode step's outputs for the active slots (aligned lists)."""

    slots: List[int]
    tokens: np.ndarray           # (n_active,)
    logprobs: np.ndarray         # (n_active,)
    positions: np.ndarray        # (n_active,) post-step cache positions


class DecodeRunner:
    """Active-slot decode via *persistent* power-of-two compaction buckets.

    When every slot is active (or ``compact=False``) this is the seed
    engine's full-batch decode: all ``max_slots`` rows in place, inactive
    rows masked. When fewer are active, the active rows are gathered into a
    ``next_pow2(n_active)`` bucket **once** and decoded there step after
    step — decode FLOPs, cache-update traffic, and sampling all scale with
    the bucket, not ``max_slots``. The compact state is written back into
    the full cache only at structural changes (occupancy change, or an
    explicit ``flush`` before a prefill scatters new rows), so the steady
    state pays one jitted dispatch per step with bucket-sized buffers.

    Coherence contract: while compact state is live, the *active* rows of
    the full cache handed back by ``run`` are stale — callers that read or
    write cache rows directly (the prefill scatter) must call ``flush``
    first. ``run`` itself re-syncs automatically whenever the active-slot
    set changes.
    """

    def __init__(self, cfg: ArchConfig, *, max_slots: int, temperature: float = 1.0):
        self.cfg = cfg
        self.max_slots = max_slots
        self.temperature = temperature
        self._jit_decode = named_jit(
            "rollout_decode", functools.partial(M.decode_step, cfg)
        )
        self._jit_gather = named_jit("rollout_gather", gather_rows)
        self._jit_sample = named_jit(
            "rollout_sample",
            lambda lg, ks: sample_rows(lg, ks, temperature=self.temperature),
        )
        # fused row-gather + decode per (bucket, n_active): one dispatch
        # per steady-state step
        self._compact_steps: Dict[Tuple[int, int], Any] = {}
        self._flushes: Dict[Tuple[int, int], Any] = {}
        # persistent compact state: (ordered active slots, compact cache)
        self._rows: Optional[Tuple[int, ...]] = None
        self._rows_arr: Optional[jax.Array] = None   # padded device copy
        self._live_arr: Optional[jax.Array] = None
        self._compact: Optional[Cache] = None
        # program spans also record here (see PrefillRunner)
        self.tracer = None

    def bucket_of(self, n_active: int) -> int:
        return min(next_pow2(max(n_active, 1)), self.max_slots)

    # ------------------------------------------------------------ coherence
    def flush(self, cache: Cache) -> Cache:
        """Write live compact rows back into ``cache`` and drop the compact
        state. Call before touching cache rows externally (prefill scatter);
        a no-op when no compact state is held."""
        if self._compact is None:
            return cache
        n = len(self._rows)
        bucket = self.bucket_of(n)
        fn = self._flushes.get((bucket, n))
        if fn is None:
            def _flush(cache, compact, live):
                live_rows = {
                    name: jax.tree_util.tree_map(
                        lambda f: jax.lax.slice_in_dim(
                            f, 0, n, axis=BATCH_AXIS[name]
                        ),
                        val,
                    )
                    for name, val in compact.items()
                }
                return scatter_rows(cache, live_rows, live)

            fn = named_jit("rollout_flush", _flush)
            self._flushes[(bucket, n)] = fn
        cache = fn(cache, self._compact, self._live_arr)
        self._rows = self._rows_arr = self._live_arr = None
        self._compact = None
        return cache

    def _compact_step(self, bucket: int, n: int):
        key = (bucket, n)
        fn = self._compact_steps.get(key)
        if fn is None:
            def step(params, last_tokens, compact, rows):
                logits, new_compact = M.decode_step(
                    self.cfg, params, last_tokens[rows], compact
                )
                return logits, new_compact, new_compact["pos"][:n]

            fn = named_jit("rollout_decode", step)
            self._compact_steps[key] = fn
        return fn

    # ----------------------------------------------------------------- step
    def run(
        self,
        params: Any,
        cache: Cache,
        active: Sequence[int],
        last_tokens: jax.Array,      # (max_slots,)
        keys: jax.Array,             # (n_active, 2) per-slot stream keys
        *,
        compact: bool = True,
    ) -> Tuple[Cache, jax.Array, DecodeResult]:
        """One decode step over ``active`` slots.

        Returns (cache, last_tokens, result); ``last_tokens`` rows of
        inactive slots are preserved, as are their cache positions.
        ``keys`` are per-slot trajectory stream keys aligned with
        ``active`` — pad/inactive rows reuse the first key, their draws
        are discarded.
        """
        active = list(active)
        n = len(active)
        bucket = self.max_slots if not compact else self.bucket_of(n)
        if bucket >= self.max_slots:
            cache = self.flush(cache)
            return self._run_full(params, cache, active, last_tokens, keys)

        with span("decode.dispatch", self.tracer, rows=n):
            rows_key = tuple(active)
            if self._rows != rows_key:
                # occupancy changed: sync the old compact state back,
                # gather the new active rows (padded with duplicates of the
                # first row; the pads decode too but are never written back)
                cache = self.flush(cache)
                self._rows_arr = jnp.asarray(
                    active + [active[0]] * (bucket - n), jnp.int32
                )
                self._live_arr = jnp.asarray(active, jnp.int32)
                self._compact = self._jit_gather(cache, self._rows_arr)
                self._rows = rows_key
            logits, self._compact, pos_live = self._compact_step(bucket, n)(
                params, last_tokens, self._compact, self._rows_arr
            )
            keys_pad = pad_keys(keys, bucket)
            tokens, blps = self._jit_sample(logits, keys_pad)
            tokens, blps = tokens[:n], blps[:n]
            last_tokens = last_tokens.at[self._live_arr].set(tokens)
        with span("decode.sync", self.tracer, rows=n):
            result = DecodeResult(
                slots=active,
                tokens=np.asarray(tokens),
                logprobs=np.asarray(blps),
                positions=np.asarray(pos_live),
            )
        return cache, last_tokens, result

    def _run_full(self, params, cache, active, last_tokens, keys):
        """Seed path: decode all ``max_slots`` rows, mask inactive ones."""
        with span("decode.dispatch", self.tracer, rows=len(active)):
            prev_pos = cache["pos"]
            logits, new_cache = self._jit_decode(params, last_tokens, cache)
            mask = np.zeros((self.max_slots,), bool)
            mask[active] = True
            mask_j = jnp.asarray(mask)
            new_cache["pos"] = jnp.where(mask_j, new_cache["pos"], prev_pos)
            keys_full = scatter_keys(keys, active, self.max_slots)
            tokens, blps = self._jit_sample(logits, keys_full)
            last_tokens = jnp.where(mask_j, tokens, last_tokens)
        with span("decode.sync", self.tracer, rows=len(active)):
            tokens_np = np.asarray(tokens)
            blps_np = np.asarray(blps)
            pos_np = np.asarray(new_cache["pos"])
        return new_cache, last_tokens, DecodeResult(
            slots=list(active),
            tokens=tokens_np[active],
            logprobs=blps_np[active],
            positions=pos_np[active],
        )


class PagedDecodeRunner:
    """Active-slot decode over a block-paged KV pool.

    The pool is shared by every slot, so — unlike ``DecodeRunner`` — no
    cache rows need gathering or persistent compaction for the KV itself:
    the per-step block-table array *is* the compaction. Active slots are
    still bucketed to ``next_pow2(n_active)`` rows so matmul cost scales
    with occupancy; only the small per-slot entries (``pos``, hybrid
    conv/ssm, audio cross caches) are gathered/scattered each step. There
    is no compact state held between steps, hence no ``flush`` coherence
    protocol either.

    A step is one program (``rollout_decode``) per ``(bucket, n_active)``:
    row gather, decode, key padding, sampling, the live-row slices and the
    ``last_tokens`` and per-slot scatters all run inside it. Its index
    inputs travel in one packed host buffer and its per-row outputs come
    back in one device -> host read (``DEVICE_CALLS``).

    Pad rows duplicate the first active slot's token/position but point
    their block tables at the null block, so their writes land in the
    garbage sink and their outputs are sliced away.
    """

    # device calls per ``run``: the packed index transfer, the program and
    # the read of its (3, n) output
    DEVICE_CALLS = 3

    def __init__(
        self,
        cfg: ArchConfig,
        *,
        max_slots: int,
        blocks_per_seq: int,
        null_block: int = 0,
        temperature: float = 1.0,
        impl: Optional[str] = None,            # kernels.ops dispatch override
        pool_sharding: Optional[Any] = None,   # pin paged K/V layout (TP)
    ):
        self.cfg = cfg
        self.max_slots = max_slots
        self.nb = blocks_per_seq
        self.null_block = null_block
        self.temperature = temperature
        self.impl = impl
        self.pool_sharding = pool_sharding
        self._steps: Dict[Tuple[int, int], Any] = {}
        # program spans also record here (see PrefillRunner)
        self.tracer = None

    def bucket_of(self, n_active: int) -> int:
        return min(next_pow2(max(n_active, 1)), self.max_slots)

    def _step(self, bucket: int, n: int):
        fn = self._steps.get((bucket, n))
        if fn is None:
            def step(params, last_tokens, cache, idx, keys):
                # idx = rows (bucket) | live (n) | tables (bucket * nb)
                rows = idx[:bucket]
                live = idx[bucket:bucket + n]
                tables = idx[bucket + n:].reshape(bucket, self.nb)
                small = {
                    nm: v for nm, v in cache.items() if nm not in ("k", "v")
                }
                view = gather_rows(small, rows)
                view["k"], view["v"] = cache["k"], cache["v"]
                logits, new = M.paged_decode_step(
                    self.cfg, gather_params(params), last_tokens[rows],
                    view, tables, impl=self.impl,
                )
                live_rows = {
                    nm: jax.tree_util.tree_map(
                        lambda f: jax.lax.slice_in_dim(
                            f, 0, n, axis=BATCH_AXIS[nm]
                        ),
                        new[nm],
                    )
                    for nm in small
                }
                out = scatter_rows(small, live_rows, live)
                out["k"], out["v"] = new["k"], new["v"]
                if self.pool_sharding is not None:
                    out["k"] = jax.lax.with_sharding_constraint(
                        out["k"], self.pool_sharding
                    )
                    out["v"] = jax.lax.with_sharding_constraint(
                        out["v"], self.pool_sharding
                    )
                tokens, blps = sample_rows(
                    logits, pad_keys(keys, bucket),
                    temperature=self.temperature,
                )
                tokens = tokens[:n]
                last_tokens = last_tokens.at[live].set(tokens)
                rows_out = jnp.stack([
                    tokens,
                    jax.lax.bitcast_convert_type(blps[:n], jnp.int32),
                    new["pos"][:n],
                ])
                return out, last_tokens, rows_out

            fn = named_jit("rollout_decode", step)
            self._steps[(bucket, n)] = fn
        return fn

    def run(
        self,
        params: Any,
        cache: Cache,
        active: Sequence[int],
        block_tables: Dict[int, Sequence[int]],   # slot -> block table
        last_tokens: jax.Array,                   # (max_slots,)
        keys: jax.Array,                          # (n_active, 2) stream keys
    ) -> Tuple[Cache, jax.Array, DecodeResult]:
        """One decode step over ``active`` slots. Returns
        (cache, last_tokens, result)."""
        active = list(active)
        n = len(active)
        with span("decode.dispatch", self.tracer, rows=n):
            bucket = self.bucket_of(n)
            idx = np.full(bucket + n + bucket * self.nb, self.null_block,
                          np.int32)
            idx[:n] = active
            idx[n:bucket] = active[0]
            idx[bucket:bucket + n] = active
            tables = idx[bucket + n:].reshape(bucket, self.nb)
            for r, slot in enumerate(active):
                bt = block_tables[slot]
                tables[r, : len(bt)] = bt
            cache, last_tokens, out = self._step(bucket, n)(
                params, last_tokens, cache, idx, keys
            )
        with span("decode.sync", self.tracer, rows=n):
            out = np.asarray(out)
            result = DecodeResult(
                slots=active,
                tokens=out[0],
                logprobs=out[1].view(np.float32),
                positions=out[2],
            )
        return cache, last_tokens, result
