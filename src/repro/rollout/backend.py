"""Engine backend contract: one instance interface for runtime + simulator.

The coordinator (``repro.core.coordinator``) is pure control plane — it
emits ``Route / Interrupt / Abort / Pull`` commands against instance
*snapshots* and never touches an engine. This module pins down the data
plane those commands land on:

``EngineBackend``
    The protocol every rollout instance implements:
    ``route / interrupt / abort / pull / step / snapshot``.  Three
    implementations ship:

    * ``repro.rollout.engine.RolloutInstance`` — the real JAX engine
      (slot-based continuous batching, batched prefill + compacted decode
      via ``repro.rollout.runners``);
    * ``repro.rollout.sharded.ShardedBackend`` — the same engine spanning
      a pod: params and the paged KV pool head-sharded over a
      ``("tensor",)`` mesh, per-device memory accounting
      (``shard_count``), bit-for-bit equal to the single-device engine;
    * ``SimBackend`` (here) — the cost-model-driven replica the
      discrete-event simulator and the baselines run on.  Token payloads
      are tracked as counts (``Trajectory.sim_generated``); timing follows
      the paper's Eq. 2 cost model.

    Real backends ignore the simulated-clock arguments (``now``/``dt``);
    simulated backends ignore the parameter payload of ``pull``.  That
    asymmetry is exactly what lets one coordinator drive a *mixed* cluster
    of real and simulated instances (``examples/mixed_cluster.py``).

``execute_commands``
    The single, backend-agnostic command executor.  The live runtime, the
    simulator, and the mixed example all route coordinator output through
    it, so command semantics (TS take/put_back/drop, PS pull) cannot drift
    between deployments.

``create_backend``
    Factory/registry keyed by backend name (``"jax"`` / ``"sim"``); the JAX
    engine is imported lazily so simulator-only workloads never pay the JAX
    import.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import (
    Any,
    Dict,
    List,
    Optional,
    Protocol,
    Sequence,
    Tuple,
    runtime_checkable,
)

from repro.core.commands import Abort, Command, Interrupt, Pull, Route
from repro.core.cost_model import CostModel
from repro.core.snapshot import InstanceSnapshot
from repro.core.types import Trajectory, TrajStatus
from repro.rollout.prefix_cache import PrefixRegistry, shareable_run


@runtime_checkable
class ParamSource(Protocol):
    """Where a backend pulls parameters from (the PS, or a version stub)."""

    @property
    def version(self) -> int: ...

    def pull(self) -> Tuple[Any, int]: ...


class VersionSource:
    """Parameter-less ``ParamSource`` for simulated backends: tracks only
    the published model version (the simulator's ``ps_version``)."""

    def __init__(self, version: int = 0):
        self.version = version

    def pull(self) -> Tuple[Any, int]:
        return None, self.version


@runtime_checkable
class EngineBackend(Protocol):
    """One rollout instance, as seen by the coordinator's command stream.

    Contract (conformance-tested in ``tests/test_backend.py``):

    * ``route(traj, now)``     — enqueue; admit when slots/KV allow. Sets
      ``traj.instance`` to this instance's id.
    * ``route_many(trajs, now)`` — enqueue a whole wave, then admit once:
      the real engine prefills every admissible trajectory in one batched
      forward per length bucket (``execute_commands`` coalesces each command
      batch's Routes per instance into one wave).
    * ``interrupt(ids, now)``  — remove matching resident trajectories and
      return them with ``status=INTERRUPTED`` and ``instance=None``; the
      payload travels on the Trajectory object (migration is metadata-only).
    * ``abort(ids, now)``      — like interrupt but ``status=ABORTED``.
    * ``pull(params, version, now)`` — adopt a new parameter version and
      clear ``complete_trajs`` accounting. Simulated backends ignore
      ``params``.
    * ``step(now, dt)``        — advance generation; returns trajectories
      completed during the step. Real backends perform one decode step and
      ignore the clock; simulated backends integrate ``dt`` sim-seconds.
    * ``snapshot()``           — the paper's five-field instance snapshot.
    """

    inst_id: int
    inst_version: int

    def route(self, traj: Trajectory, now: float = 0.0) -> None: ...

    def route_many(
        self, trajs: Sequence[Trajectory], now: float = 0.0
    ) -> None: ...

    def interrupt(
        self, traj_ids: Sequence[int], now: float = 0.0
    ) -> List[Trajectory]: ...

    def abort(
        self, traj_ids: Sequence[int], now: float = 0.0
    ) -> List[Trajectory]: ...

    def pull(self, params: Any, version: int, now: float = 0.0) -> None: ...

    def step(self, now: float = 0.0, dt: float = 0.0) -> List[Trajectory]: ...

    def snapshot(self) -> InstanceSnapshot: ...


# ============================================================== sim backend
class SimBackend:
    """Cost-model-driven rollout replica (the simulator's data plane).

    Decode progress follows ``CostModel.step_latency`` (paper Eq. 2);
    admission respects the KV budget; routing/migration re-prefill stalls
    the instance for ``length / prefill_tps`` and Pull for ``pull_time``.
    """

    def __init__(
        self,
        inst_id: int,
        cost_model: CostModel,
        version: int = 0,
        *,
        prefill_tps: float = 50000.0,
        pull_time: float = 0.0,
        admission_headroom_tokens: int = 64,
        share_prefix: bool = True,
        lazy_cow: bool = True,
    ):
        self.inst_id = inst_id
        self.cm = cost_model
        self.inst_version = version
        self._prefill_tps = prefill_tps
        self.pull_time = pull_time
        # decode-growth tokens charged on top of a trajectory's current
        # length at admission (see RolloutInstance.admission_headroom_tokens;
        # the sim's coarser dt steps warrant a larger default)
        self.admission_headroom_tokens = admission_headroom_tokens
        # prefix sharing mirrors the paged engine's group admission: a run
        # of same-group, same-prompt, nothing-generated members at the
        # waiting-queue head admits as one unit — one prefill stall, shared
        # prompt blocks charged once. Inert at block_size 1 (dense model).
        self.share_prefix = bool(share_prefix and cost_model.block_size > 1)
        # lazy CoW mirror: a group's partial-tail block is charged once
        # until members diverge (first decode progress), matching the
        # engine's copy-at-first-divergence pool accounting
        self.lazy_cow = bool(lazy_cow and self.share_prefix)
        self.running: Dict[int, Trajectory] = {}
        self.progress: Dict[int, float] = {}   # fractional generated tokens
        self.waiting: List[Trajectory] = []
        self.stall_until = 0.0
        self.complete_since_sync: set = set()
        self.decode_tokens = 0.0
        self.prefill_tokens = 0.0
        # the part of prefill_tokens forwarded for trajectories that had
        # already generated (RolloutInstance.reprefill_tokens)
        self.reprefill_tokens = 0.0
        self.preemptions = 0                   # sim pools never preempt
        self.shared_prefix_hits = 0
        self.block_copies = 0                  # mirrored CoW tail copies
        # observability hook (same protocol as RolloutInstance.on_admit)
        self.on_admit = None
        # shared-prefix registry — the same class the engine maintains, so
        # both admission pictures and snapshot exports come from one
        # implementation and cannot drift
        self._prefix = PrefixRegistry()

    # ------------------------------------------------------------- geometry
    @property
    def version(self) -> int:  # legacy alias
        return self.inst_version

    def kv_bytes(self) -> float:
        """Per-device KV bytes in use, at the cost model's allocation
        granularity (block-rounded when ``cm.block_size`` > 1 — the same
        accounting the paged RolloutInstance reports, so mixed real/sim
        clusters give the coordinator one consistent memory picture; at
        ``cm.shard_count`` > 1 the same per-device basis the sharded
        backend reports). Shared prefix blocks are charged once per
        group, like the engine's refcounted pool."""
        bs = self.cm.block_size
        total = self.cm.token_bytes(float(self._prefix.shared_token_total()))
        tails = self._prefix.export_tails() if self.lazy_cow else {}
        # each prefix with undiverged members holds ONE shared tail block
        total += self.cm.token_bytes(float(bs * len(tails)))
        for t in self.running.values():
            pk = self._prefix.lookup(t.traj_id)
            if pk is None:
                total += self.cm.kv_bytes_for(t.length)
            else:
                n_full = self._prefix.tokens(pk) // bs
                excl = max(0, -(-t.length // bs) - n_full)
                if t.traj_id in tails.get(pk, ()):
                    # undiverged: the tail block is the shared one above
                    excl = max(0, excl - 1)
                total += self.cm.token_bytes(bs * excl)
        return total

    def n_active(self) -> int:
        return len(self.running)

    def _share_run(self) -> int:
        """Shareable same-group run length at the queue head — the same
        scan the engine runs (``prefix_cache.shareable_run``)."""
        if not self.share_prefix:
            return 1
        return shareable_run(self.waiting)

    def _admit_one(self, traj: Trajectory, now: float, prefill: int) -> None:
        self.running[traj.traj_id] = traj
        self.progress[traj.traj_id] = float(traj.sim_generated)
        if prefill:
            self.stall_until = (
                max(self.stall_until, now) + prefill / self._prefill_tps
            )
            self.prefill_tokens += prefill
            if traj.length > len(traj.prompt):
                self.reprefill_tokens += prefill
        if self.on_admit is not None:
            self.on_admit(self.inst_id, [traj.traj_id])

    def _admit(self, now: float) -> None:
        while self.waiting:
            g = self._share_run()
            if g >= 2:
                head = self.waiting[0]
                plen = len(head.prompt)
                pad = plen + self.admission_headroom_tokens
                while g >= 2:
                    charge = self.cm.group_kv_bytes_for(plen, [pad] * g)
                    if self.kv_bytes() + charge <= self.cm.kv_budget:
                        break
                    g -= 1
                if g >= 2:
                    members = [self.waiting.pop(0) for _ in range(g)]
                    bs = self.cm.block_size
                    n_full, tail = divmod(plen, bs)
                    lazy_tail = self.lazy_cow and tail > 0
                    if n_full or lazy_tail:
                        ids = [m.traj_id for m in members]
                        self._prefix.register(
                            head.group_id, ids, n_full * bs, head.prompt,
                            tail_members=ids if lazy_tail else (),
                        )
                    # one shared prompt prefill for the whole group
                    self._admit_one(members[0], now, prefill=plen)
                    for m in members[1:]:
                        self._admit_one(m, now, prefill=0)
                    self.shared_prefix_hits += g - 1
                    continue
            nxt = self.waiting[0]
            # cross-wave join: a straggler member of a still-resident
            # prefix is charged only its exclusive blocks (the engine
            # forks the sibling prefix the same way)
            fork_pk = None
            if (
                self.share_prefix
                and nxt.group_id >= 0
                and not nxt.sim_generated
            ):
                h, tp = nxt.prompt_key()
                fork_pk = self._prefix.find(
                    nxt.group_id, tp, prompt_hash=h
                )
                if (
                    fork_pk is not None
                    and self._prefix.tokens(fork_pk) == 0
                ):
                    fork_pk = None  # tail-only registration: no prefix
            charge = self.cm.kv_bytes_for(
                nxt.length + self.admission_headroom_tokens
            )
            if fork_pk is not None:
                charge = max(
                    0.0,
                    charge - self.cm.token_bytes(self._prefix.tokens(fork_pk)),
                )
            if self.kv_bytes() + charge > self.cm.kv_budget:
                return
            self.waiting.pop(0)
            if fork_pk is not None:
                self._prefix.join(fork_pk, nxt.traj_id)
                self.shared_prefix_hits += 1
            # re-prefill stall (prompt + already-generated tokens)
            self._admit_one(nxt, now, prefill=nxt.length)

    # ------------------------------------------------------------- commands
    def route(self, traj: Trajectory, now: float = 0.0) -> None:
        traj.instance = self.inst_id
        traj.status = TrajStatus.RUNNING
        self.waiting.append(traj)
        self._admit(now)

    def route_many(
        self, trajs: Sequence[Trajectory], now: float = 0.0
    ) -> None:
        for traj in trajs:
            traj.instance = self.inst_id
            traj.status = TrajStatus.RUNNING
            self.waiting.append(traj)
        self._admit(now)

    def _remove(self, traj_ids: Sequence[int], now: float) -> List[Trajectory]:
        out = []
        for tid in list(traj_ids):
            if tid in self.running:
                t = self.running.pop(tid)
                t.sim_generated = int(self.progress.pop(tid))
                self._prefix.drop(tid)
                out.append(t)
            else:
                for i, t in enumerate(self.waiting):
                    if t.traj_id == tid:
                        out.append(self.waiting.pop(i))
                        break
        self._admit(now)
        return out

    def interrupt(
        self, traj_ids: Sequence[int], now: float = 0.0
    ) -> List[Trajectory]:
        out = self._remove(traj_ids, now)
        for t in out:
            t.status = TrajStatus.INTERRUPTED
            t.instance = None
        return out

    def abort(self, traj_ids: Sequence[int], now: float = 0.0) -> List[Trajectory]:
        out = self._remove(traj_ids, now)
        for t in out:
            t.status = TrajStatus.ABORTED
            t.instance = None
        return out

    def pull(self, params: Any, version: int, now: float = 0.0) -> None:
        del params  # simulated replicas carry no real weights
        self.inst_version = version
        self.complete_since_sync.clear()
        self.stall_until = max(self.stall_until, now) + self.pull_time

    # ----------------------------------------------------------------- step
    def step(self, now: float = 0.0, dt: float = 0.0) -> List[Trajectory]:
        """Generate tokens for ``dt`` sim-seconds; return completed trajs."""
        if not self.running:
            return []
        t0 = max(now, self.stall_until)
        avail = now + dt - t0
        if avail <= 0:
            return []
        lat = self.cm.step_latency(self.kv_bytes(), len(self.running))
        steps = avail / lat
        if self.lazy_cow:
            # divergence mirror: every running member writes its first
            # decode token this step, copying the shared tail into a
            # private block (the last undiverged owner writes in place)
            for tid in self.running:
                if self._prefix.in_shared_tail(tid):
                    pk = self._prefix.lookup(tid)
                    if pk is not None and self._prefix.undiverged(pk) > 1:
                        self.block_copies += 1
                    self._prefix.mark_diverged(tid)
        done = []
        for tid, traj in list(self.running.items()):
            self.progress[tid] += steps
            self.decode_tokens += steps
            traj.sim_generated = int(self.progress[tid])
            if self.progress[tid] >= traj.sim_target_len:
                traj.sim_generated = traj.sim_target_len
                traj.finished = True
                traj.status = TrajStatus.GENERATED
                del self.running[tid]
                del self.progress[tid]
                self._prefix.drop(tid)
                self.complete_since_sync.add(tid)
                done.append(traj)
        if done:
            self._admit(now + dt)
        return done

    # ------------------------------------------------------------- snapshot
    def snapshot(self) -> InstanceSnapshot:
        lengths = {t.traj_id: t.length for t in self.running.values()}
        lengths.update({t.traj_id: t.length for t in self.waiting})
        prefix_groups, prefix_tokens = self._prefix.export()
        return InstanceSnapshot(
            inst_id=self.inst_id,
            kv_cache=self.kv_bytes(),
            run_trajs=set(self.running),
            wait_trajs={t.traj_id for t in self.waiting},
            complete_trajs=set(self.complete_since_sync),
            inst_version=self.inst_version,
            traj_lengths=lengths,
            preemptions=0,  # sim pools admit by budget, never preempt
            prefix_groups=prefix_groups,
            prefix_tokens=prefix_tokens,
            prefix_tail_members=self._prefix.export_tails(),
            shard_count=self.cm.shard_count,
        )


# ================================================================= executor
@dataclass
class ExecResult:
    """What a command batch did — shared telemetry for runtime and sim."""

    routed: int = 0
    interrupted: int = 0
    aborted: int = 0
    pulls: List[Tuple[int, int]] = field(default_factory=list)  # (inst, version)
    returned: List[int] = field(default_factory=list)           # put_back ids
    # Routes whose trajectory left the routable pool between issuance and
    # execution (possible only under concurrent schedulers); the caller
    # must rebalance the speculative state for these (inst, traj_id) pairs
    skipped_routes: List[Tuple[int, int]] = field(default_factory=list)
    # Interrupt/Abort targets the engine no longer held at execution time
    # (completed or already removed since the snapshot — possible only
    # under relaxed/streaming snapshot collection). The command had no
    # data-plane effect, so the caller must undo its speculative decrement
    # unless a later Pull in the same batch re-zeroed the expectation.
    missed_removals: List[Tuple[int, int]] = field(default_factory=list)


def execute_commands(
    commands: Sequence[Command],
    instances: Dict[int, EngineBackend],
    ts,                                   # TrajectoryServer
    param_source: ParamSource,
    *,
    now: float = 0.0,
    timers: Optional[Dict[str, float]] = None,
    lifecycle=None,                       # TrajectoryLifecycle (optional)
) -> ExecResult:
    """Apply coordinator commands to any mix of engine backends.

    Missing instances (failed since command issuance) are skipped, matching
    the live runtime's fault-tolerance semantics.

    Consecutive Route commands are coalesced per instance and applied as
    one ``route_many`` wave, letting the real engine admit every routed
    trajectory in one batched prefill per length bucket. Pending waves are
    flushed before any non-Route command executes, so semantics match the
    strictly in-order executor for *arbitrary* command sequences — with
    the coordinator's ordering (Alg. 1 emits Routes last within a cycle)
    the whole cycle still lands as one wave per instance.

    With a ``lifecycle`` bus, command execution *publishes* the trajectory
    transitions (``ROUTED`` / ``INTERRUPTED`` / ``ABORTED``, with ``inst``
    set to mark the data plane as already handled) and the TS applies its
    side as a subscriber; without one, the executor calls the TS directly
    (legacy standalone mode).
    """
    res = ExecResult()

    def _timed(name: str, t0: float) -> None:
        if timers is not None:
            timers[name] = timers.get(name, 0.0) + time.perf_counter() - t0

    route_waves: Dict[int, List[Trajectory]] = {}

    def _flush_waves() -> None:
        for inst_id, wave in route_waves.items():
            t0 = time.perf_counter()
            # publish ROUTED before the data-plane route: ``route_many``
            # may admit synchronously, and admission-time observers (the
            # tracer's on_admit hook) need the span opened first
            if lifecycle is not None:
                for traj in wave:
                    lifecycle.routed(traj, inst_id, traj.v_traj)
            instances[inst_id].route_many(wave, now)
            _timed("route", t0)
        route_waves.clear()

    for cmd in commands:
        inst = instances.get(cmd.inst)
        if inst is None:
            continue  # instance failed since issuance
        if isinstance(cmd, Route):
            t0 = time.perf_counter()
            for tid in cmd.traj_ids:
                if lifecycle is not None:
                    traj = ts.try_take(tid)
                    if traj is None:
                        res.skipped_routes.append((cmd.inst, tid))
                        continue
                else:
                    traj = ts.take(tid)
                if traj.v_traj is None:
                    traj.v_traj = cmd.v_traj
                route_waves.setdefault(cmd.inst, []).append(traj)
                res.routed += 1
            _timed("route", t0)
            continue
        _flush_waves()
        if isinstance(cmd, Interrupt):
            t0 = time.perf_counter()
            removed = set()
            for traj in inst.interrupt(cmd.traj_ids, now):
                removed.add(traj.traj_id)
                if lifecycle is not None:
                    lifecycle.interrupted(traj, cmd.inst)
                else:
                    ts.put_back(traj.traj_id)
                res.returned.append(traj.traj_id)
            res.interrupted += len(cmd.traj_ids)
            res.missed_removals.extend(
                (cmd.inst, tid) for tid in cmd.traj_ids if tid not in removed
            )
            _timed("interrupt", t0)
        elif isinstance(cmd, Abort):
            removed = {t.traj_id for t in inst.abort(cmd.traj_ids, now)}
            for tid in cmd.traj_ids:
                if lifecycle is not None:
                    lifecycle.aborted(tid, inst=cmd.inst)
                else:
                    ts.drop(tid)
            res.aborted += len(cmd.traj_ids)
            res.missed_removals.extend(
                (cmd.inst, tid) for tid in cmd.traj_ids if tid not in removed
            )
        elif isinstance(cmd, Pull):
            t0 = time.perf_counter()
            params, version = param_source.pull()
            inst.pull(params, version, now)
            res.pulls.append((cmd.inst, version))
            _timed("pull", t0)

    _flush_waves()
    return res


# ================================================================== factory
def _make_sim_backend(inst_id: int, **kw) -> SimBackend:
    return SimBackend(inst_id, **kw)


def _make_jax_backend(inst_id: int, **kw) -> "EngineBackend":
    from repro.rollout.engine import RolloutInstance  # lazy: needs jax

    return RolloutInstance(inst_id, **kw)


def _make_sharded_backend(inst_id: int, **kw) -> "EngineBackend":
    from repro.rollout.sharded import ShardedBackend  # lazy: needs jax

    return ShardedBackend(inst_id, **kw)


BACKENDS = {
    "sim": _make_sim_backend,
    "jax": _make_jax_backend,
    "sharded": _make_sharded_backend,
}


def create_backend(kind: str, inst_id: int, **kw) -> EngineBackend:
    """Construct a rollout instance by backend name
    (``"jax"`` / ``"sim"`` / ``"sharded"``).

    Keyword arguments are backend-specific: the JAX engine takes
    ``cfg/params/version/max_slots/...`` (see ``RolloutInstance``), the
    sharded engine additionally ``shard_count``/``mesh``
    (see ``ShardedBackend``), the sim backend
    ``cost_model/version/prefill_tps/pull_time``.
    """
    try:
        factory = BACKENDS[kind]
    except KeyError:
        raise ValueError(
            f"unknown backend {kind!r}; available: {sorted(BACKENDS)}"
        ) from None
    return factory(inst_id, **kw)
