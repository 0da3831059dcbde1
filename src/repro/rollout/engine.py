"""Rollout instance: a model replica with slot-based continuous batching.

This is the JAX stand-in for a vLLM instance in the paper's rollout service
(DESIGN.md hardware-adaptation table). One instance owns:

* a parameter snapshot + its model version (``inst_version``),
* a decode cache with ``max_slots`` rows (continuous batching: trajectories
  occupy slots; finished/interrupted slots are reused),
* an engine-internal waiting queue (the paper's ``wait_trajs``, Fig. 11) —
  trajectories routed to the instance but not yet admitted to a slot
  (KV budget or slot exhaustion), and
* a prefill/decode runner pair (``repro.rollout.runners``): admission
  prefills **all** eligible waiting trajectories in one padded forward per
  length bucket and scatters the row caches in one fused jitted write;
  decode gathers only the **active** slots into a power-of-two compaction
  bucket instead of always stepping ``max_slots`` rows.

``RolloutInstance`` implements the ``EngineBackend`` protocol
(``repro.rollout.backend``): ``route / interrupt / abort / pull / step /
snapshot``. The simulated-clock arguments of ``step``/``pull`` are accepted
and ignored — a real replica advances one decode step per ``step()`` call.

Command execution (the data-plane side of §5.1):
* ``route``     — enqueue; admit into a free slot if the KV budget allows
                  (re-)prefilling prompt+partial response (partial rollout
                  re-prefill, Fig. 5a).
* ``interrupt`` — release slots/queue entries; the Trajectory object already
                  carries its generated tokens + behavior logprobs, so
                  returning it to the TS is metadata-only.
* ``abort``     — like interrupt, but the trajectory is discarded upstream.
* ``pull``      — swap parameters/version. The coordinator interrupts
                  residents first (Alg. 1), so slots are empty by contract.

Behavior logprobs: every sampled token's logprob under the *generating*
version is recorded on the trajectory — this is the importance-sampling
denominator for staleness correction (``repro.rl.losses``) and survives
interrupts/migrations untouched.

Paged KV mode (``paged=True``): the dense ``(max_slots, max_len)`` cache
rows are replaced by a shared block pool + per-trajectory block tables
(``repro.rollout.kv_allocator``). Admission charges the budget by *actual
allocated blocks* instead of worst-case rows, decode extends tables on the
fly as trajectories cross block boundaries, and block exhaustion preempts
the youngest resident back to the waiting queue (it re-admits via the
normal re-prefill path — the same interrupt semantics the coordinator
uses). Greedy decode is bit-for-bit equal to the dense path
(``tests/test_engine_equivalence.py``).

Prefix sharing (``share_prefix=True``, paged mode): group-sampled
trajectories (GRPO/DAPO) that share one prompt and arrive together admit as
a **group**: the prompt is prefilled once, its full KV blocks are mapped
read-only into every member's block table (refcounted,
``repro.rollout.prefix_cache``), and the partially-filled tail block is
device-copied per member (eager copy-on-write) so decode appends never
alias. Frees and preemption decrement refcounts; ``kv_bytes()`` charges
shared blocks once. Greedy (and same-occupancy stochastic) decode stays
bit-for-bit equal to ``group_size`` independent prefills.

Legacy mode: ``batched_prefill=False`` forces single-row prefill groups and
``compact_decode=False`` forces full-``max_slots`` decode — together they
reproduce the seed engine's execution exactly, which the equivalence tests
(``tests/test_engine_equivalence.py``) compare the batched path against.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Callable, Deque, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.snapshot import InstanceSnapshot
from repro.core.types import Trajectory, TrajStatus
from repro.data.tokenizer import EOS
from repro.models import model as M
from repro.obs.spans import span
from repro.rollout.kv_allocator import (
    NULL_BLOCK,
    BlockExhausted,
    blocks_for_tokens,
)
from repro.rollout.prefix_cache import (
    PrefixRegistry,
    RefcountedBlockAllocator,
    shareable_run,
)
from repro.rollout.runners import (
    DecodeRunner,
    PagedDecodeRunner,
    PrefillJob,
    PrefillRunner,
)
from repro.rollout.sampler import stream_key, stream_keys


class RolloutInstance:
    def __init__(
        self,
        inst_id: int,
        cfg: ArchConfig,
        params: Any,
        version: int,
        *,
        max_slots: int = 4,
        max_len: int = 256,
        kv_bytes_per_token: float = 0.0,
        kv_budget: float = float("inf"),
        temperature: float = 1.0,
        eos_id: int = EOS,
        seed: int = 0,
        prefill_bucket: int = 16,
        frontend_fn: Optional[Callable[[int], jax.Array]] = None,
        batched_prefill: bool = True,
        compact_decode: bool = True,
        paged: bool = False,
        kv_block_size: int = 16,
        kv_pool_blocks: Optional[int] = None,
        admission_headroom_tokens: int = 16,
        share_prefix: bool = True,
        lazy_cow: bool = True,
        shard_count: int = 1,
    ):
        self.inst_id = inst_id
        self.cfg = cfg
        self.params = params
        self.inst_version = version
        self.max_slots = max_slots
        self.max_len = max_len
        self.k5 = kv_bytes_per_token or (
            2 * cfg.n_layers * cfg.n_kv_heads * cfg.hd * 4
        )
        # Devices this instance spans (ShardedBackend sets > 1). ``k5``
        # stays the *total* per-token KV footprint across the pod; memory
        # accounting and ``kv_budget`` are per-device, so every charge
        # uses ``k5_local`` and the coordinator sees one device's HBM.
        self.shard_count = shard_count
        self.k5_local = self.k5 / shard_count
        self.kv_budget = kv_budget
        self.temperature = temperature
        self.eos_id = eos_id
        self.compact_decode = compact_decode
        # Admission over-provisioning: besides its current tokens, a routed
        # trajectory is charged this many future decode tokens against the
        # KV budget, so freshly admitted trajectories have room to grow
        # before the next coordinator cycle rebalances (avoids immediate
        # OOM-thrash at full budget). The charge is capped at ``max_len``.
        self.admission_headroom_tokens = admission_headroom_tokens
        self.paged = paged
        self.kv_block_size = kv_block_size
        # Per-slot PRNG key streams: the key for a trajectory's p-th
        # sampled token is fold_in(fold_in(base, traj_id), p) — a pure
        # function of (seed, traj_id, position). Deliberately NOT mixed
        # with inst_id: a trajectory's stochastic stream must be identical
        # wherever it decodes, so migration/compaction are invariant.
        self._base_key = jax.random.PRNGKey(seed)

        # vlm caches lead with ``n_patches`` frontend positions per slot
        self._pos_offset = (
            cfg.n_patches
            if (cfg.family == "vlm" and frontend_fn is not None)
            else 0
        )
        # prefix sharing needs the paged pool and a plain token frontend
        # (frontend embeddings would have to be proven identical per row)
        self.share_prefix = bool(share_prefix and paged and frontend_fn is None)
        # lazy CoW: group tails stay shared until each member's first
        # decode write (copy-at-first-divergence) instead of being copied
        # eagerly at admission
        self.lazy_cow = bool(lazy_cow and self.share_prefix)
        # suffix prefill: fork admissions forward only the tokens past the
        # resident shared prefix. Gated to families whose forward carries
        # no per-position recurrent/cross state (see paged_prefill_step).
        self._suffix_ok = self.share_prefix and cfg.family in ("dense", "moe")
        self.allocator: Optional[RefcountedBlockAllocator] = None
        if paged:
            bs = kv_block_size
            blocks_per_seq = blocks_for_tokens(max_len, bs)
            if kv_pool_blocks is not None:
                n_blocks = kv_pool_blocks
            elif kv_budget != float("inf"):
                # per-device budget over per-device block bytes
                n_blocks = int(kv_budget // (self.k5_local * bs))
            else:
                n_blocks = max_slots * blocks_per_seq
            # at least one max-length trajectory must always fit, so block
            # exhaustion can only strike when there is a victim to preempt
            n_blocks = max(n_blocks, blocks_per_seq)
            # refcounted allocator: identical to the plain pool without
            # sharing, and the substrate for group-admission prefix reuse
            self.allocator = RefcountedBlockAllocator(n_blocks + 1, bs)
            self.cache = M.init_paged_cache(
                cfg, max_slots, max_len, n_blocks + 1, bs
            )
        else:
            self.cache = M.init_cache(cfg, max_slots, max_len)
        self.slots: List[Optional[Trajectory]] = [None] * max_slots
        # deque: admission pops the head and preemption pushes the head on
        # hot loops — both O(1) (a list pays O(n) per pop(0)/insert(0))
        self.waiting: Deque[Trajectory] = deque()
        self.complete_since_sync: set = set()
        self._last_tokens = jnp.zeros((max_slots,), jnp.int32)
        # incrementally maintained byte counter (exact under paging via the
        # allocator; on the dense path updated at admission / per recorded
        # token / slot release) — admission is O(1) per trajectory instead
        # of O(active slots)
        self._kv_bytes = 0.0
        # per-slot cache position (host mirror of cache["pos"] rows) and
        # admission sequence number (preemption picks the youngest resident)
        self._slot_pos: List[int] = [0] * max_slots
        self._slot_seq: List[int] = [0] * max_slots
        self._admit_seq = 0
        # shared-prefix registry (shared with SimBackend): prefix id ->
        # member traj ids still holding the shared full prompt blocks +
        # their token capacity. Exported in snapshots so the coordinator's
        # discard releases shared bytes once per group, and consulted by
        # single admissions to fork a still-resident sibling prefix.
        self._prefix = PrefixRegistry()
        # telemetry
        self.decode_steps = 0
        self.prefill_tokens = 0
        # tokens forwarded again at admission for trajectories that had
        # already generated (re-admission after a Pull's interrupt, or a
        # preemption), on the prefill_tokens basis
        self.reprefill_tokens = 0
        # device calls the paged decode step issues (programs, host ->
        # device transfers, eager ops, blocking reads): the stream_keys
        # program, whose host inputs ride in its call, plus the runner's
        # DEVICE_CALLS
        self.decode_device_calls = 0
        self.decode_tokens = 0
        self.preemptions = 0
        self.shared_prefix_hits = 0       # members admitted off a shared prompt
        self.prefill_tokens_saved = 0     # prompt tokens not re-prefilled
        self.block_copies = 0             # CoW pool-block copies issued
        # observability hooks (set by the runtime when tracing is on):
        # on_admit(inst_id, traj_ids) after waiting trajectories enter
        # decode slots; on_preempt(inst_id, traj_id) on KV eviction
        self.on_admit = None
        self.on_preempt = None

        # runner construction goes through overridable factories so the
        # sharded backend swaps in its SPMD variants without duplicating
        # the argument plumbing (one construction site for both backends)
        self.prefill_runner = self._make_prefill_runner(
            cfg,
            max_len=max_len,
            prefill_bucket=prefill_bucket,
            batch_limit=0 if batched_prefill else 1,
            temperature=temperature,
            frontend_fn=frontend_fn,
            paged_block_size=kv_block_size if paged else 0,
        )
        if paged:
            self.paged_decode_runner = self._make_paged_decode_runner(
                cfg,
                max_slots=max_slots,
                blocks_per_seq=blocks_for_tokens(max_len, kv_block_size),
                temperature=temperature,
            )
            self.decode_runner = None
        else:
            self.paged_decode_runner = None
            self.decode_runner = DecodeRunner(
                cfg, max_slots=max_slots, temperature=temperature
            )
        self._overflow_done: List[Trajectory] = []
        self.tracer = None

    @property
    def tracer(self):
        """The ``TrajectoryTracer`` that the program spans
        (``repro.obs.spans``) of the instance and its runners also record
        into; None: the profiler's trace only."""
        return self._tracer

    @tracer.setter
    def tracer(self, tracer) -> None:
        self._tracer = tracer
        for runner in (
            self.prefill_runner, self.paged_decode_runner, self.decode_runner
        ):
            if runner is not None:
                runner.tracer = tracer

    # --------------------------------------------------- runner factories
    def _make_prefill_runner(self, cfg: ArchConfig, **kw) -> PrefillRunner:
        return PrefillRunner(cfg, **kw)

    def _make_paged_decode_runner(
        self, cfg: ArchConfig, **kw
    ) -> PagedDecodeRunner:
        return PagedDecodeRunner(cfg, **kw)

    # ------------------------------------------------------------- geometry
    def _slot_len(self, t: Trajectory) -> int:
        return t.length

    def kv_bytes(self) -> float:
        """Bytes of KV in use *per device* — O(1).

        Paged: exact block-granular usage (allocated blocks x block bytes,
        divided across the pod's head shards). Dense: token-granular sum
        over resident trajectories, maintained incrementally (dense mode
        is single-device only).
        """
        if self.paged:
            return self.k5_local * self.allocator.used_tokens()
        return self._kv_bytes

    def _recompute_kv_bytes(self) -> float:
        """O(active-slots) dense recomputation — invariant checks in tests."""
        return sum(
            self.k5 * self._slot_len(t) for t in self.slots if t is not None
        )

    def n_active(self) -> int:
        return sum(1 for t in self.slots if t is not None)

    # ------------------------------------------------------------- commands
    def route(self, traj: Trajectory, now: float = 0.0) -> None:
        traj.instance = self.inst_id
        self.waiting.append(traj)
        self._admit()

    def route_many(
        self, trajs: Sequence[Trajectory], now: float = 0.0
    ) -> None:
        """Enqueue a wave of trajectories, then admit once — every
        admissible trajectory prefills in one batched forward per bucket."""
        for traj in trajs:
            traj.instance = self.inst_id
            self.waiting.append(traj)
        self._admit()

    def _release_slot(self, slot: int) -> Trajectory:
        """Vacate ``slot`` and release its KV (blocks or byte counter).

        Under paging the free *decrements refcounts*: blocks shared with
        surviving group members stay allocated until the last member
        releases them."""
        t = self.slots[slot]
        self.slots[slot] = None
        if self.paged:
            self.allocator.free(t.traj_id)
            self._prefix.drop(t.traj_id)
        else:
            self._kv_bytes = max(
                0.0, self._kv_bytes - self.k5 * self._slot_len(t)
            )
        return t

    def interrupt(
        self, traj_ids: Sequence[int], now: float = 0.0
    ) -> List[Trajectory]:
        ids = set(traj_ids)
        out: List[Trajectory] = []
        for i, t in enumerate(self.slots):
            if t is not None and t.traj_id in ids:
                self._release_slot(i)
                t.status = TrajStatus.INTERRUPTED
                t.instance = None
                out.append(t)
        keep = []
        for t in self.waiting:
            if t.traj_id in ids:
                t.status = TrajStatus.INTERRUPTED
                t.instance = None
                out.append(t)
            else:
                keep.append(t)
        self.waiting = deque(keep)
        self._admit()
        return out

    def abort(self, traj_ids: Sequence[int], now: float = 0.0) -> List[Trajectory]:
        out = self.interrupt(traj_ids)
        for t in out:
            t.status = TrajStatus.ABORTED
        return out

    def pull(self, params: Any, version: int, now: float = 0.0) -> None:
        with span("engine.pull", self._tracer, inst=self.inst_id):
            self.params = params
            self.inst_version = version
            self.complete_since_sync.clear()
            # residents were interrupted by the coordinator beforehand
            # (Alg. 1); anything left is re-prefilled lazily on its next
            # admit
            self._admit()

    # ---------------------------------------------------------------- admit
    def _admission_charge(self, length: int) -> float:
        """Bytes a routed trajectory of ``length`` tokens is charged at
        admission (current tokens + ``admission_headroom_tokens`` of growth,
        capped at ``max_len``; block-rounded under paging).

        The paged charge is on the *cache-position* basis the allocator
        draws from — including the vlm patch offset — so the budget check
        matches what ``alloc`` will actually take. Dense keeps the seed's
        token basis (its ``kv_bytes`` excludes patches too)."""
        tokens = min(length + self.admission_headroom_tokens, self.max_len)
        if self.paged:
            bs = self.kv_block_size
            return self.k5_local * bs * blocks_for_tokens(
                min(tokens + self._pos_offset, self.max_len), bs
            )
        return self.k5_local * tokens

    def _share_run(self) -> int:
        """Shareable same-group run length at the waiting-queue head (the
        scan itself is shared with SimBackend; prompts at the engine-level
        overflow cap finish immediately instead)."""
        if not self.share_prefix:
            return 1
        return shareable_run(self.waiting, self.max_len - 1)

    def _admit_group(
        self,
        g: int,
        free: List[int],
        jobs: List["PrefillJob"],
        trajs: List[Trajectory],
        planned_bytes: float,
    ) -> Optional[float]:
        """Try to admit the first ``g`` waiting trajectories (one group,
        one prompt) as a shared-prefix unit. Shrinks ``g`` until budget and
        pool fit; returns updated ``planned_bytes``, or ``None`` when even
        the shrunken unit cannot admit (caller falls back to the single
        path, whose FIFO break semantics then apply)."""
        bs = self.kv_block_size
        prompt = self.waiting[0].prompt
        cache_len = len(prompt)
        n_full, tail = divmod(cache_len, bs)
        pad_tokens = min(cache_len + self.admission_headroom_tokens,
                         self.max_len)
        member_excl = blocks_for_tokens(pad_tokens, bs) - n_full
        while g >= 2:
            # the budget/pool decision stays worst-case (every member
            # eventually diverges and owns a private tail) so lazy and
            # eager CoW admit identical schedules
            charge = self.k5_local * bs * (n_full + g * member_excl)
            need_now = n_full + (g if tail else 0)
            if (
                planned_bytes + charge <= self.kv_budget
                and need_now <= self.allocator.n_free
            ):
                break
            g -= 1
        if g < 2:
            return None
        members = [self.waiting.popleft() for _ in range(g)]
        slots = [free.pop(0) for _ in range(g)]
        # per-member stream keys in one batched dispatch (position =
        # n_generated, 0 for fresh members)
        karr = stream_keys(
            self._base_key,
            jnp.asarray([m.traj_id for m in members], jnp.uint32),
            jnp.asarray([m.n_generated for m in members], jnp.uint32),
        )
        keys = [karr[i] for i in range(g)]
        ids = [m.traj_id for m in members]
        shared, tails = self.allocator.alloc_group(
            ids, cache_len, lazy_tail=self.lazy_cow
        )
        planned_bytes += self.k5_local * bs * (len(shared) + len(tails))
        lazy_tail = bool(tails) and self.lazy_cow
        if shared or lazy_tail:
            # a lazy shared tail must be registered even with zero full
            # shared blocks — divergence tracking hangs off the registry
            self._prefix.register(
                members[0].group_id, ids, len(shared) * bs, prompt,
                tail_members=ids if lazy_tail else (),
            )
        jobs.append(PrefillJob(
            slot=slots[0],
            tokens=list(prompt),
            key=keys[0],
            blocks=shared + tails[:1],
            extra_slots=slots[1:],
            extra_keys=keys[1:],
            tail_src=tails[0] if tails else None,
            tail_dsts=tails[1:],
        ))
        trajs.extend(members)
        self.shared_prefix_hits += g - 1
        self.prefill_tokens_saved += (g - 1) * cache_len
        return planned_bytes

    def _admit(self) -> None:
        """Admit waiting trajectories into free slots within the KV budget —
        all eligible admissions run as ONE batched prefill per length bucket.

        Admission policy matches the seed engine decision-for-decision on
        the dense path: the waiting queue is FIFO, each admission charges
        its headroom-padded current length against the budget and then
        accumulates ``k5 * (length + 1)`` of planned usage (the +1 is the
        token prefill samples), and a trajectory too long to generate
        consumes its candidate slot index exactly as the seed's slot-scan
        did. Under paging the charge is the trajectory's *actual block
        allocation*, and admission additionally requires the pool to hold
        enough free blocks for the (re-)prefill.

        Prefix sharing: a contiguous run of same-group, same-prompt,
        nothing-generated members at the queue head admits as one unit —
        one prompt prefill, full blocks shared (refcounted), private tail
        copies — charging the shared blocks once.
        """
        if not self.waiting:
            return
        with span(
            "engine.admit", self._tracer, inst=self.inst_id,
            waiting=len(self.waiting),
        ):
            free = [i for i, t in enumerate(self.slots) if t is None]
            jobs: List[PrefillJob] = []
            trajs: List[Trajectory] = []
            planned_bytes = self.kv_bytes()
            while self.waiting and free:
                run = min(self._share_run(), len(free))
                if run >= 2:
                    planned = self._admit_group(
                        run, free, jobs, trajs, planned_bytes
                    )
                    if planned is not None:
                        planned_bytes = planned
                        continue
                nxt = self.waiting[0]
                tokens = list(nxt.prompt) + list(nxt.response)
                cache_len = len(tokens) + self._pos_offset
                # cross-wave prefix join: a straggler group member admitted
                # after its siblings forks their still-resident prefix blocks
                # instead of duplicating them. On suffix-capable families only
                # the tokens past the resident prefix are forwarded; otherwise
                # the full forward runs with its full-block KV writes discarded
                # into the null sink. A preempted member re-admitting with a
                # partial response forks too — the shared prefix covers its
                # prompt, and the suffix is the prompt tail plus the response.
                fork_pk = None
                shared_blocks = 0
                if (
                    self.paged
                    and self.share_prefix
                    and len(tokens) < self.max_len - 1
                    and nxt.group_id >= 0
                    and not nxt.sim_generated
                ):
                    h, tp = nxt.prompt_key()
                    fork_pk = self._prefix.find(
                        nxt.group_id, tp, prompt_hash=h
                    )
                    if fork_pk is not None:
                        shared_blocks = (
                            self._prefix.tokens(fork_pk) // self.kv_block_size
                        )
                        if shared_blocks == 0:
                            fork_pk = None  # tail-only registration: no prefix
                charge = self._admission_charge(self._slot_len(nxt))
                charge -= self.k5_local * self.kv_block_size * shared_blocks
                if planned_bytes + max(charge, 0.0) > self.kv_budget:
                    break
                if self.paged:
                    # ``alloc`` below draws down ``n_free`` as this pass admits,
                    # so the availability check is against the live free count
                    need_blocks = (
                        blocks_for_tokens(cache_len, self.kv_block_size)
                        - shared_blocks
                    )
                    if (
                        len(tokens) < self.max_len - 1
                        and need_blocks > self.allocator.n_free
                    ):
                        break  # pool exhausted: wait for releases
                self.waiting.popleft()
                slot = free.pop(0)
                if len(tokens) >= self.max_len - 1:
                    # no room to generate: finish immediately (engine-level cap)
                    nxt.finished = True
                    nxt.status = TrajStatus.GENERATED
                    self.complete_since_sync.add(nxt.traj_id)
                    self._overflow_done.append(nxt)
                    continue
                sub = self._sample_key(nxt)
                blocks = None
                suffix_start: Optional[int] = None
                resident_tokens = 0
                if self.paged:
                    if fork_pk is not None:
                        shared = self.allocator.table(
                            self._prefix.member_of(fork_pk)
                        )[:shared_blocks]
                        own = self.allocator.fork(nxt.traj_id, shared, cache_len)
                        self._prefix.join(fork_pk, nxt.traj_id)
                        if self._suffix_ok:
                            # suffix prefill: forward only the tokens past the
                            # resident prefix — the real block table is passed
                            # so attention reads the donor's resident KV.
                            # Block-aligned forks re-forward one prompt token
                            # for logits; its redundant K/V write is redirected
                            # to the null sink inside paged_prefill_step.
                            resident_tokens = shared_blocks * self.kv_block_size
                            suffix_start = min(resident_tokens, len(tokens) - 1)
                            blocks = shared + own
                            self.prefill_tokens_saved += suffix_start
                        else:
                            # scatter target: the shared blocks are already
                            # written (identical prompt KV) — aim those rows at
                            # the null garbage block, keep only tail/own writes
                            blocks = [NULL_BLOCK] * shared_blocks + own
                        planned_bytes += self.k5_local * self.kv_block_size * len(own)
                        self.shared_prefix_hits += 1
                    else:
                        blocks = self.allocator.alloc(nxt.traj_id, cache_len)
                        planned_bytes += (
                            self.k5_local * self.kv_block_size * len(blocks)
                        )
                else:
                    planned_bytes += self.k5_local * (self._slot_len(nxt) + 1)
                jobs.append(PrefillJob(
                    slot=slot, tokens=tokens, key=sub, blocks=blocks,
                    suffix_start=suffix_start, resident_tokens=resident_tokens,
                ))
                trajs.append(nxt)
            if not jobs:
                return
            if not self.paged:
                # the decode runner may hold active rows compacted out of the
                # batch cache; sync them back before the prefill scatter writes
                # new rows (the paged pool needs no such coherence step)
                self.cache = self.decode_runner.flush(self.cache)
            self.cache, result = self.prefill_runner.run(
                self.params, self.cache, jobs
            )
            self.prefill_tokens += result.prefill_tokens
            self.block_copies += result.tail_copies
            member_slots: List[int] = []
            member_lens: List[int] = []
            for job in jobs:
                if trajs[len(member_slots)].n_generated:
                    # prompt and partial response forwarded again
                    self.reprefill_tokens += job.forwarded
                member_slots.append(job.slot)
                member_slots.extend(job.extra_slots)
                member_lens.extend([len(job.tokens)] * job.n_members)
            last = self._last_tokens
            for slot, n_tok, traj, tok, blp in zip(
                member_slots, member_lens, trajs, result.tokens, result.logprobs
            ):
                self._record_token(traj, tok, blp)
                last = last.at[slot].set(tok)
                traj.status = TrajStatus.RUNNING
                self.slots[slot] = traj
                self._slot_pos[slot] = n_tok + self._pos_offset
                self._slot_seq[slot] = self._admit_seq
                self._admit_seq += 1
                if not self.paged:
                    self._kv_bytes += self.k5 * self._slot_len(traj)
            self._last_tokens = last
            if self.on_admit is not None:
                self.on_admit(self.inst_id, [t.traj_id for t in trajs])

    # ----------------------------------------------------------------- step
    def _sample_key(self, traj: Trajectory) -> jax.Array:
        """Stream key for the trajectory's NEXT sampled token (position =
        tokens generated so far, so a re-prefilled partial rollout resumes
        its stream exactly where the interrupt cut it)."""
        return stream_key(self._base_key, traj.traj_id, traj.n_generated)

    def _record_token(self, traj: Trajectory, token: int, blp: float) -> None:
        traj.response.append(token)
        traj.behavior_logprobs.append(blp)
        traj.record_segment(self.inst_version, 1)
        if token == self.eos_id or traj.n_generated >= traj.max_new_tokens:
            traj.finished = True

    def _preempt(self, slot: int) -> None:
        """Evict ``slot``'s trajectory to the head of the waiting queue,
        releasing its blocks (it re-prefills prompt + partial response on
        re-admission — the standard partial-rollout path)."""
        t = self._release_slot(slot)
        t.status = TrajStatus.INTERRUPTED
        self.waiting.appendleft(t)
        self.preemptions += 1
        if self.on_preempt is not None:
            self.on_preempt(self.inst_id, t.traj_id)

    def _ensure_decode_blocks(self) -> None:
        """Grow each resident's block table to cover its next write
        position; on pool exhaustion preempt the *youngest* resident
        (vLLM-style LIFO preemption — the oldest trajectories, closest to
        completion, keep their blocks).

        Lazy CoW: a group member still pointing at its group's shared tail
        block diverges here, at its first decode write — the tail is copied
        into a private block *before* the decode dispatch so the write
        cannot clobber siblings. The last undiverged owner writes in place
        (no copy needed: nobody else reads the block anymore)."""
        copies: List[Tuple[int, int]] = []
        for slot in sorted(
            (i for i, t in enumerate(self.slots) if t is not None),
            key=lambda i: self._slot_seq[i],
        ):
            t = self.slots[slot]
            if t is None:  # preempted earlier in this pass
                continue
            while True:
                try:
                    self.allocator.extend_to(t.traj_id, self._slot_pos[slot] + 1)
                    if self.lazy_cow and self._prefix.in_shared_tail(
                        t.traj_id
                    ):
                        # first write lands in the shared tail block
                        # (tail member => no decode writes yet => next
                        # write position is inside the prompt's tail)
                        pair = self.allocator.cow(
                            t.traj_id,
                            self._slot_pos[slot] // self.kv_block_size,
                        )
                        self._prefix.mark_diverged(t.traj_id)
                        if pair is not None:
                            copies.append(pair)
                    break
                except BlockExhausted:
                    victims = [
                        i
                        for i, v in enumerate(self.slots)
                        if v is not None and i != slot
                    ]
                    if not victims:
                        # unreachable by construction: the pool holds >= one
                        # full-length trajectory's worth of blocks, and with
                        # every victim preempted this owner is the sole
                        # surviving table (shared refcounts drop to 1 with
                        # it), so free >= blocks_per_seq - len(table) >=
                        # the <= 1 block the extension needs. A preempted
                        # sharer may free 0 blocks, but the loop then moves
                        # to the next victim rather than re-preempting it.
                        raise
                    self._preempt(max(victims, key=lambda i: self._slot_seq[i]))
        if copies:
            self.block_copies += len(copies)
            self.cache = self.prefill_runner.copy_blocks(self.cache, copies)

    def step(self, now: float = 0.0, dt: float = 0.0) -> List[Trajectory]:
        """One batched decode step over the active slots. Returns completed
        trajectories (removed from their slots)."""
        with span("engine.step", self._tracer, inst=self.inst_id):
            done: List[Trajectory] = []
            if self._overflow_done:
                done.extend(self._overflow_done)
                self._overflow_done.clear()
            if self.paged:
                with span("engine.blocks", self._tracer, inst=self.inst_id):
                    self._ensure_decode_blocks()
            active = [i for i, t in enumerate(self.slots) if t is not None]
            if not active:
                return done
            # host arrays ride into the program's call: no eager
            # transfers of their own
            keys = stream_keys(
                self._base_key,
                np.asarray(
                    [self.slots[s].traj_id for s in active], np.uint32
                ),
                np.asarray(
                    [self.slots[s].n_generated for s in active], np.uint32
                ),
            )
            if self.paged:
                tables = {
                    s: self.allocator.table(self.slots[s].traj_id)
                    for s in active
                }
                self.cache, self._last_tokens, result = (
                    self.paged_decode_runner.run(
                        self.params, self.cache, active, tables,
                        self._last_tokens, keys,
                    )
                )
                self.decode_device_calls += (
                    1 + self.paged_decode_runner.DEVICE_CALLS
                )
            else:
                self.cache, self._last_tokens, result = (
                    self.decode_runner.run(
                        self.params,
                        self.cache,
                        active,
                        self._last_tokens,
                        keys,
                        compact=self.compact_decode,
                    )
                )
            self.decode_steps += 1
            self.decode_tokens += len(active)

            with span("engine.record", self._tracer, inst=self.inst_id,
                      rows=len(active)):
                for slot, token, blp, pos in zip(
                    result.slots, result.tokens, result.logprobs,
                    result.positions,
                ):
                    traj = self.slots[slot]
                    self._record_token(traj, int(token), float(blp))
                    self._slot_pos[slot] = int(pos)
                    if not self.paged:
                        self._kv_bytes += self.k5
                    if traj.finished or int(pos) >= self.max_len - 1:
                        traj.finished = True
                        traj.status = TrajStatus.GENERATED
                        self.complete_since_sync.add(traj.traj_id)
                        done.append(traj)
                        self._release_slot(slot)
            if done:
                self._admit()
            return done

    # ------------------------------------------------------------- snapshot
    def snapshot(self) -> InstanceSnapshot:
        run = {t.traj_id for t in self.slots if t is not None}
        lengths = {
            t.traj_id: self._slot_len(t)
            for t in list(self.slots) + list(self.waiting)
            if t is not None
        }
        # cumulative preemption count — snapshot() stays a pure read; the
        # coordinator differences consecutive snapshots into the per-cycle
        # rate the routing penalty wants
        prefix_groups, prefix_tokens = self._prefix.export()
        return InstanceSnapshot(
            inst_id=self.inst_id,
            kv_cache=self.kv_bytes(),
            run_trajs=run,
            wait_trajs={t.traj_id for t in self.waiting},
            complete_trajs=set(self.complete_since_sync),
            inst_version=self.inst_version,
            traj_lengths=lengths,
            preemptions=self.preemptions,
            prefix_groups=prefix_groups,
            prefix_tokens=prefix_tokens,
            prefix_tail_members=self._prefix.export_tails(),
            shard_count=self.shard_count,
        )
