"""Scheduler-agnostic runtime core: the wired service graph of Fig. 6.

``RuntimeCore`` owns every component of the disaggregated deployment —
trajectory server, parameter server, staleness manager, coordinator, reward
server, N rollout instances, the training worker — and the **trajectory
lifecycle bus** that connects them, but no control loop. Control loops live
in ``repro.runtime.schedulers``:

* ``CooperativeScheduler`` — the deterministic single-threaded tick
  (decode -> reward -> coordinate -> train -> refill), preserving the seed
  runtime's interleaving bit-for-bit;
* ``ThreadedScheduler``    — rollout instances, reward workers, the
  coordinator, and the trainer on separate threads, which is what the
  paper's architecture actually runs.

Service wiring (everything below is a bus subscription, not a call chain):

    instance.step() completes T
      -> lifecycle.COMPLETED ─ TS marks GENERATED
                             └ RewardServer scores (inline or worker pool)
           -> lifecycle.REWARDED ─ RetiredPayloadStore retains payload
                                 └ coordinator: protocol Occupy; surplus ->
                -> lifecycle.ABORTED ─ TS drops
                                     ├ RetiredPayloadStore evicts
                                     └ core aborts on every instance
    coordinator.try_consume()
      -> lifecycle.CONSUMED ─ TS retires registry slots

Thread safety: every instance is wrapped in a ``LockedBackend``; the
coordinator's lock is held across a whole snapshot->command->execute cycle
(with all instance locks), so Eq. 1's speculative-state validation holds
under real concurrency exactly as it does cooperatively.
"""
from __future__ import annotations

import time
from contextlib import ExitStack
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import witness as lock_witness
from repro.analysis.witness import make_lock, make_rlock
from repro.configs.base import ArchConfig
from repro.core import (
    Abort,
    Pull,
    CostModel,
    ParameterServer,
    RetiredPayloadStore,
    RewardServer,
    RewardServerConfig,
    RolloutCoordinator,
    StalenessManager,
    TrajectoryLifecycle,
    TrajectoryServer,
    prefix_routing_strategy,
    routing_strategy,
)
from repro.core.lifecycle import LifecycleEvent, LifecycleEventKind
from repro.core.snapshot import collect as collect_snapshots
from repro.data.tasks import ArithmeticDataset
from repro.models import model as M
from repro.obs.spans import span
from repro.reward.verifier import RewardModel
from repro.rl.advantages import group_advantages
from repro.rollout.backend import EngineBackend, create_backend, execute_commands
from repro.runtime.config import RuntimeConfig, StepRecord
from repro.training import checkpoint as ckpt_lib
from repro.training.optimizer import AdamWConfig, init_opt_state
from repro.training.train_step import TRAINER_IMPL, make_rl_train_step


class LockedBackend:
    """An ``EngineBackend`` behind one RLock.

    Each rollout instance is single-threaded *internally* but is touched
    by several services (its decode thread, the coordinator's command
    executor, protocol-initiated aborts). The lock serializes those; every
    other attribute (telemetry counters, ``allocator`` etc.) passes through
    untouched.

    ``retire()`` marks a failed replica dead under its own lock: a decode
    thread still holding the handle (it fetched it before ``fail_instance``
    popped it from the fleet) sees its next ``step()`` return nothing
    instead of generating on trajectories the TS already reclaimed.
    """

    def __init__(self, inner: EngineBackend):
        self.inner = inner
        # order-keyed: barrier cycles enter several instance locks, always
        # in ascending inst_id order (the sorted ExitStack below) — the
        # witness checks the key ordering at runtime
        self.lock = make_rlock("instance", order_key=inner.inst_id)
        self._retired = False

    def __getattr__(self, name: str) -> Any:
        return getattr(self.inner, name)

    def retire(self) -> None:
        with self.lock:
            self._retired = True

    def route(self, *a, **kw):
        with self.lock:
            return self.inner.route(*a, **kw)

    def route_many(self, *a, **kw):
        with self.lock:
            return self.inner.route_many(*a, **kw)

    def interrupt(self, *a, **kw):
        with self.lock:
            return self.inner.interrupt(*a, **kw)

    def abort(self, *a, **kw):
        with self.lock:
            return self.inner.abort(*a, **kw)

    def pull(self, *a, **kw):
        with self.lock:
            return self.inner.pull(*a, **kw)

    def step(self, *a, **kw):
        with self.lock:
            if self._retired:
                return []
            return self.inner.step(*a, **kw)

    def snapshot(self, *a, **kw):
        with self.lock:
            return self.inner.snapshot(*a, **kw)


class RuntimeCore:
    """The wired, scheduler-agnostic async-RL system (see module docstring)."""

    def __init__(self, cfg: ArchConfig, rcfg: RuntimeConfig):
        self.cfg = cfg
        self.rcfg = rcfg
        # opt-in lock-order witness: must activate before any service
        # below constructs its locks, so every lock joins the tracked set
        if rcfg.lock_witness:
            lock_witness.enable()
        key = jax.random.PRNGKey(rcfg.seed)
        self.params = M.init_params(cfg, key)
        self.opt_state = init_opt_state(self.params)
        # only the optimizer state is donated: the parameter server and the
        # rollout instances hold the same param arrays (stale versions
        # stay alive up to eta), so params must survive the step. The
        # trainer lock keeps checkpoint() from reading a donated state.
        self._train_lock = make_lock("trainer")
        self.train_step = jax.jit(
            make_rl_train_step(
                cfg, AdamWConfig(lr=rcfg.lr), objective=rcfg.objective,
                impl=TRAINER_IMPL,
            ),
            donate_argnums=(1,),
        )

        # ------------------------------------------------- the service bus
        self.lifecycle = TrajectoryLifecycle()
        # coordinator-cycle dirty flag: any lifecycle event (or decode
        # progress, marked in decode_instance) means the next cycle may
        # have work; a quiet system lets coordinator_cycle short-circuit
        # without re-sorting and re-entering every instance lock
        self._coord_dirty = True
        self._coord_last_ps_version = -1
        for _kind in LifecycleEventKind:
            self.lifecycle.subscribe(_kind, self._mark_coord_dirty)

        self.dataset = ArithmeticDataset(rcfg.n_prompts, seed=rcfg.seed)
        if rcfg.reward_fn is not None:
            self.reward_model = type(
                "CustomReward", (), {"score": staticmethod(rcfg.reward_fn)}
            )()
        else:
            self.reward_model = RewardModel(
                lambda prompt: self.dataset.answer_for(prompt)
            )
        self.manager = StalenessManager(batch_size=rcfg.batch_size, eta=rcfg.eta)
        # ------------------------------------------- observability plane
        # Opt-in registry + tracer (repro.obs). Disabled (default): the
        # registry is the shared no-op and the tracer stays None, so every
        # instrumentation site below is a None-check or a no-op call and
        # the seed paths are byte-identical. The tracer subscribes to the
        # lifecycle bus *before* the TS/reward/protocol handlers attach,
        # so its timestamps mark event publication, not dispatch tails.
        self.obs_enabled = bool(rcfg.observability or rcfg.trace_path)
        if self.obs_enabled:
            from repro.obs import MetricsRegistry, TrajectoryTracer

            self.metrics = MetricsRegistry()
            self.tracer: Optional[TrajectoryTracer] = TrajectoryTracer(
                self.lifecycle,
                # CONSUMED events are published under the coordinator lock
                # right after consume() advanced the floor: the consumed
                # batch's floor is train_version - 1 (see tracer docstring)
                floor_source=lambda: self.manager.train_version,
                registry=self.metrics,
            )
        else:
            from repro.obs.metrics import NOOP_REGISTRY

            self.metrics = NOOP_REGISTRY
            self.tracer = None
        self._m_staleness = self.metrics.histogram(
            "consumed_staleness", buckets=tuple(range(0, 17))
        )
        self.ts = TrajectoryServer(
            self.dataset.prompt_source(),
            capacity_groups=(rcfg.eta + 1) * rcfg.batch_size,
            group_size=rcfg.group_size,
            max_new_tokens=rcfg.max_new_tokens,
        )
        # subscription order fixes the per-event dispatch order; it mirrors
        # the seed runtime's call order (TS transition first, then payload
        # retention, then protocol, then instance cleanup)
        self.ts.attach(self.lifecycle)
        self.retired = RetiredPayloadStore(self.lifecycle)
        # ------------------------------------------------------ reward hub
        # Verifier resolution: an explicit rcfg.verifier wins; score_url /
        # score_sandbox auto-build a RewardHub around the in-process
        # RewardModel; otherwise the RewardModel scores directly (seed
        # behavior, bit-for-bit).
        verifier = rcfg.verifier
        if verifier is None and (rcfg.score_url or rcfg.score_sandbox):
            verifier = self._build_reward_hub()
        if verifier is None:
            verifier = self.reward_model
        from repro.reward.hub import RewardHub as _RewardHub

        self.reward_hub: Optional[_RewardHub] = (
            verifier if isinstance(verifier, _RewardHub) else None
        )
        self.reward_server = RewardServer(
            verifier,
            self.lifecycle,
            RewardServerConfig(
                n_workers=rcfg.reward_workers,
                queue_capacity=rcfg.reward_queue_capacity,
                simulated_latency=rcfg.reward_latency,
            ),
            # aborted-while-queued completions are dropped, not scored
            liveness=lambda t: self.ts.get(t.traj_id) is not None,
            metrics=self.metrics,
            tracer=self.tracer,
            # terminal verification failure (hub on_failure="abort"):
            # release the protocol entry + publish group-wide ABORTED.
            # Deferred attribute lookup: the coordinator is built below.
            on_abort=lambda traj: self.coordinator.abort_unverifiable(traj),
        )
        self.ps = ParameterServer()
        self.ps.push(self.params, 0)
        # schedulers may swap in a BackgroundPusher (overlapped Push)
        self._push_fn: Callable[[Any, int], None] = self.ps.push

        if rcfg.rollout_shards > 1 and not rcfg.paged_kv:
            raise ValueError(
                "rollout_shards > 1 requires paged_kv=True (the sharded "
                "backend shards the paged K/V pool)"
            )
        self._rollout_mesh = None
        if rcfg.rollout_shards > 1:
            from repro.launch.mesh import make_rollout_mesh

            self._rollout_mesh = make_rollout_mesh(rcfg.rollout_shards)
        k5 = 2.0 * cfg.n_layers * cfg.n_kv_heads * cfg.hd * 4
        # kv_budget is per device: the pod-wide pool (max_len * max_slots
        # worth of k5-sized tokens) spreads evenly over the head shards
        self.cost_model = CostModel(
            k1=1e-12, k2=1e-3, k3=1e-4, k4=5e-3, k5=k5,
            kv_budget=k5 * rcfg.max_len * rcfg.max_slots
            / rcfg.rollout_shards,
            block_size=rcfg.kv_block_size if rcfg.paged_kv else 1,
            shard_count=rcfg.rollout_shards,
            # admission stops at the engines' slot pool: short trajectories
            # would let the byte budget overcommit into engine wait queues,
            # and resident waiters zero marginal_gain for every later
            # routing decision (the streaming fast path in particular)
            max_concurrency=rcfg.max_slots,
        )
        group_filter = None
        if rcfg.filter_zero_signal:
            def group_filter(members) -> bool:
                rs = [m.reward for m in members if m.reward is not None]
                return len(set(rs)) > 1
        suite = rcfg.suite
        if (
            rcfg.share_prefix
            and rcfg.paged_kv
            and rcfg.group_size > 1
            and suite.routing is routing_strategy
        ):
            # group-affine routing: members of one sampling group land on a
            # single instance so its paged engine prefills the prompt once
            import dataclasses as _dc

            suite = _dc.replace(suite, routing=prefix_routing_strategy)
        self.coordinator = RolloutCoordinator(
            self.manager,
            self.ts,
            cost_model=self.cost_model,
            cfg=rcfg.strategy_cfg,
            suite=suite,
            group_sampling=rcfg.group_size > 1,
            group_filter=group_filter,
            lifecycle=self.lifecycle,
        )
        # protocol-initiated aborts (surplus / filtering, inst=None) must
        # release engine residency everywhere; command-executed aborts
        # (inst set) already did
        self.lifecycle.subscribe(LifecycleEventKind.ABORTED, self._on_aborted)
        # streaming pipeline: freed capacity (COMPLETED; ABORTED is handled
        # inside _on_aborted, which knows which instance actually released
        # the trajectory) triggers an incremental admission decision.
        # Subscribed after the TS/reward/protocol handlers so scoring and
        # Occupy have cascaded before the routing decision looks at the
        # staleness discriminator.
        if rcfg.streaming:
            self.lifecycle.subscribe(
                LifecycleEventKind.COMPLETED, self._on_stream_completed
            )

        self._instances_lock = make_rlock("instances")
        self.instances: Dict[int, LockedBackend] = {}
        for i in range(rcfg.n_instances):
            self.instances[i] = self._new_instance(i)
        self.coordinator.spec.resync(self._snapshots())

        self._history_lock = make_lock("history")
        self.history: List[StepRecord] = []
        self.model_version = 0
        self._tick = 0
        self.ts.refill()
        # telemetry for the time-breakdown benchmark; decode/reward are
        # updated from N instance threads, so those adds take a lock
        self.timers: Dict[str, float] = {
            "decode": 0.0, "reward": 0.0, "train": 0.0,
            "coordinator": 0.0, "pull": 0.0, "route": 0.0, "interrupt": 0.0,
        }
        self._timers_lock = make_lock("timers")
        # witness violations already projected onto the tracer (so each
        # offending stack becomes exactly one trace activity)
        self._witness_exported = 0

    # -------------------------------------------------------------- plumbing
    def _build_reward_hub(self):
        """Auto-wire a RewardHub from score_url / score_sandbox flags.

        Routes: "math" -> in-process RewardModel; "code" -> sandboxed
        subprocess verifier (when score_sandbox); "remote" -> HTTP
        submit-then-poll judge (when score_url), which also becomes the
        default route — otherwise the RewardModel keeps the default.
        """
        from repro.reward import (
            DEFAULT_ROUTE,
            CircuitBreaker,
            HttpVerifier,
            RetryPolicy,
            RewardHub,
            SandboxVerifier,
        )

        rcfg = self.rcfg
        hub = RewardHub(
            default=self.reward_model,
            on_failure=rcfg.reward_on_failure,
            fallback_score=rcfg.reward_fallback_score,
            metrics=self.metrics,
            tracer=self.tracer,
        )
        hub.register("math", self.reward_model)
        if rcfg.score_sandbox:
            hub.register("code", SandboxVerifier.from_spec(
                rcfg.score_sandbox, timeout_s=rcfg.reward_timeout_s,
            ))
        if rcfg.score_url:
            remote = HttpVerifier(
                rcfg.score_url,
                policy=RetryPolicy(
                    max_attempts=max(1, rcfg.reward_retries),
                    request_timeout_s=rcfg.reward_timeout_s,
                ),
                breaker=CircuitBreaker(),
                total_timeout_s=rcfg.reward_timeout_s * 4,
                seed=rcfg.seed,
            )
            hub.register("remote", remote)
            hub.register(DEFAULT_ROUTE, remote)
        return hub

    @property
    def _retired(self) -> Dict[int, Any]:
        """Back-compat view of the retired-payload store (tests/benchmarks
        inspected the runtime's old private dict)."""
        return self.retired.payloads()

    def _new_instance(self, inst_id: int) -> LockedBackend:
        kw = dict(
            cfg=self.cfg,
            params=self.ps.pull()[0],
            version=self.ps.version,
            max_slots=self.rcfg.max_slots,
            max_len=self.rcfg.max_len,
            kv_bytes_per_token=self.cost_model.k5,
            kv_budget=self.cost_model.kv_budget,
            temperature=self.rcfg.temperature,
            seed=self.rcfg.seed,
            paged=self.rcfg.paged_kv,
            kv_block_size=self.rcfg.kv_block_size,
            share_prefix=self.rcfg.share_prefix,
        )
        if self.rcfg.rollout_shards > 1:
            backend = create_backend(
                "sharded",
                inst_id,
                shard_count=self.rcfg.rollout_shards,
                mesh=self._rollout_mesh,
                **kw,
            )
        else:
            backend = create_backend("jax", inst_id, **kw)
        if self.tracer is not None:
            # admission/preemption hooks split each span's queue-wait from
            # its decode segments, and the engine's program spans record
            # into the tracer too (set on the inner engine: LockedBackend
            # only forwards attribute *reads*)
            backend.on_admit = self.tracer.on_admit
            backend.on_preempt = self.tracer.on_preempt
            backend.tracer = self.tracer
        return LockedBackend(backend)

    def _snapshots(self):
        with self._instances_lock:
            return collect_snapshots(self.instances)

    def _mark_coord_dirty(self, e: LifecycleEvent) -> None:
        self._coord_dirty = True

    def _on_stream_completed(self, e: LifecycleEvent) -> None:
        self.stream_admit(e.inst)

    def _on_aborted(self, e: LifecycleEvent) -> None:
        if e.inst is not None:
            return  # executed as a command: the target instance is clean
        with self._instances_lock:
            handles = list(self.instances.values())
        freed: Optional[int] = None
        for h in handles:
            if h.abort([e.traj_id]):
                freed = h.inst_id
        if self.rcfg.streaming and freed is not None:
            # a protocol abort released KV blocks outside any cycle:
            # refill the freed instance within this event dispatch
            self.stream_admit(freed)

    # --------------------------------------------------------- rollout side
    def decode_instance(self, inst_id: int, n_steps: int = 1) -> int:
        """Advance one instance ``n_steps`` decode steps and push every
        completion into the lifecycle (reward phase onward). Returns the
        number of completed trajectories."""
        with self._instances_lock:
            handle = self.instances.get(inst_id)
        if handle is None:
            return 0
        t0 = time.perf_counter()
        done = []
        with span(f"decode[{inst_id}]", self.tracer, steps=n_steps):
            for _ in range(n_steps):
                done.extend(handle.step())
        t1 = time.perf_counter()
        with self._timers_lock:
            self.timers["decode"] += t1 - t0
        if handle.n_active() > 0:
            # resident KV grew: migration/routing inputs changed even
            # without a completion, so the next cycle must run
            self._coord_dirty = True
        for traj in done:
            self.complete_trajectory(traj)
        return len(done)

    def instance_busy(self, inst_id: int) -> bool:
        """Does the instance have active decode slots right now? (Lock-free
        telemetry read for the event-driven scheduler's idle decision.)"""
        with self._instances_lock:
            handle = self.instances.get(inst_id)
        return handle is not None and handle.n_active() > 0

    def complete_trajectory(self, traj) -> None:
        """Publish a completion; the reward phase (and everything behind
        it) hangs off the event. Silently skips trajectories aborted since
        generation finished (surplus/filtering race)."""
        if self.ts.get(traj.traj_id) is None:
            return
        t0 = time.perf_counter()
        s0 = self.reward_server.score_time
        self.lifecycle.completed(traj, traj.instance)
        # timers["reward"] keeps the seed runtime's meaning — time spent
        # *scoring* — not the whole dispatch (which also runs Occupy and
        # abort fan-out): inline mode charges the verifier's delta,
        # threaded mode the (tiny) enqueue cost
        if self.reward_server.threaded:
            dt = time.perf_counter() - t0
        else:
            dt = self.reward_server.score_time - s0
        with self._timers_lock:
            self.timers["reward"] += dt

    # ------------------------------------------------------ coordinator side
    def coordinator_cycle(self) -> int:
        """One snapshot->command->execute cycle. Returns the number of
        commands executed.

        Barrier mode (default): atomic under the coordinator lock AND every
        instance lock — decode, reward events, and elasticity cannot
        interleave between observation and effect (the live analog of the
        simulator's zero-time cycle).

        Streaming mode: the cycle is the rarer background *rebalance* pass
        (sync, migration, surplus aborts). Per-instance snapshots are
        collected without the all-locks barrier, so decode threads keep
        stepping while the coordinator deliberates; races are resolved at
        execute time — vanished Route targets via ``ts.try_take`` /
        ``skipped_routes``, vanished Interrupt/Abort targets via
        ``missed_removals`` — and the speculative state is compensated for
        both so Eq. 1 keeps validating.

        Short-circuit: with no routable work, no lifecycle event or decode
        progress since the last cycle, and no new parameter version, a full
        cycle is provably a no-op — skip it without re-sorting and
        re-entering every instance lock.
        """
        if (
            not self._coord_dirty
            and self.ts.n_available == 0
            and self.ps.version == self._coord_last_ps_version
        ):
            return 0
        with span("cycle", self.tracer), self.coordinator.lock:
            # reset *before* snapshotting: events landing mid-cycle re-mark
            # the flag, so their effects are observed by the next cycle
            self._coord_dirty = False
            ps_version = self.ps.version
            with self._instances_lock:
                handles = dict(self.instances)
            if self.rcfg.streaming:
                n = self._cycle_body(handles, ps_version)
            else:
                with ExitStack() as stack:
                    for i in sorted(handles):
                        stack.enter_context(handles[i].lock)
                    n = self._cycle_body(handles, ps_version)
            self._coord_last_ps_version = ps_version
            return n

    def _cycle_body(self, handles: Dict[int, LockedBackend], ps_version: int) -> int:
        t0 = time.perf_counter()
        with span("coordinator.decide", self.tracer):
            snaps = collect_snapshots(handles)
            commands = self.coordinator.step(snaps, ps_version)
        # RPL003 fix: the coordinator thread's add races the instance
        # threads' locked decode/reward adds (and run()'s final read)
        with self._timers_lock:
            self.timers["coordinator"] += time.perf_counter() - t0
        with span("coordinator.execute", self.tracer, commands=len(commands)):
            res = execute_commands(
                commands, handles, self.ts, self.ps,
                timers=self.timers, lifecycle=self.lifecycle,
            )
        # a Route that found its trajectory already gone (cross-cycle
        # failure races; any concurrent mutation under streaming's relaxed
        # snapshots) must not skew P
        for inst, tid in res.skipped_routes:
            self.coordinator.spec.apply(Abort(inst, (tid,)))
        if res.missed_removals:
            # an Interrupt/Abort whose target completed between the relaxed
            # snapshot and execution had no data-plane effect: undo its
            # speculative decrement — unless a later Pull for the same
            # instance re-zeroed the expectation (sync interrupts), in
            # which case both sides already agree
            pulled = {c.inst for c in commands if isinstance(c, Pull)}
            for inst, tid in res.missed_removals:
                if inst not in pulled:
                    self.coordinator.spec.ensure(inst).accum_traj_num += 1
        return len(commands)

    def stream_admit(self, inst_id: Optional[int]) -> int:
        """Event-driven incremental admission (streaming fast path).

        An instance freed KV capacity (COMPLETED / protocol ABORTED): make
        a single-instance routing decision under only the coordinator lock
        plus that instance's lock and execute it within this event
        dispatch — the rest of the fleet never stops decoding. Returns the
        number of Route commands executed.
        """
        if inst_id is None or not self.rcfg.streaming:
            return 0
        if self.coordinator.in_cycle():
            # emitted from a running cycle's own command execution: that
            # cycle already routes against the freed capacity
            return 0
        with self._instances_lock:
            handle = self.instances.get(inst_id)
        if handle is None:
            return 0
        t0 = time.perf_counter()
        with self.coordinator.lock:
            with handle.lock:
                with span("coordinator.decide", self.tracer):
                    snap = handle.snapshot()
                    commands = self.coordinator.route_instance(
                        snap, self.ps.version
                    )
                if commands:
                    with span("stream_admit", self.tracer,
                              routes=len(commands)):
                        res = execute_commands(
                            commands, {inst_id: handle}, self.ts, self.ps,
                            lifecycle=self.lifecycle,
                        )
                    for inst, tid in res.skipped_routes:
                        self.coordinator.spec.apply(Abort(inst, (tid,)))
        with self._timers_lock:
            self.timers["coordinator"] += time.perf_counter() - t0
        return len(commands)

    # ----------------------------------------------------------- the trainer
    def train_once(self) -> Optional[StepRecord]:
        t0 = time.perf_counter()
        # streaming consumption: rewarded groups drain into the train-floor
        # buffer (the staleness-ordered ready queue, bounded at
        # (eta+1)*capacity entries) and a partial batch ships once
        # stream_min_fill occupied entries — or the eta bound — is reached
        min_fill = self.rcfg.stream_min_fill if self.rcfg.streaming else None
        if not self.manager.ready(min_fill):
            return None
        with span("train_step", self.tracer, step=self.model_version + 1):
            batch_ids = self.coordinator.try_consume(min_fill)
            if batch_ids is None:
                return None
            # consume retires trajectories from the TS registry; payloads
            # were retained by the RetiredPayloadStore at reward time
            staleness_hist = list(self.manager.consumed_staleness[-1])
            trajs = self.retired.take(batch_ids)
            with span("trainer.batch", self.tracer, rows=len(trajs)):
                batch = self._batch_from_trajs(trajs)
            if batch is None:
                return None
            # the span closes (into the tracer) after the trainer lock,
            # a terminal leaf of the lock order, is released
            with span("trainer.dispatch", self.tracer), self._train_lock:
                self.params, self.opt_state, metrics = self.train_step(
                    self.params, self.opt_state, batch
                )
            self.model_version += 1
            with span("trainer.push", self.tracer):
                self._push_fn(self.params, self.model_version)
            # the first host read of the step's results waits for it:
            # timers["train"] is the step's host-clock time
            with span("trainer.sync", self.tracer):
                loss = float(metrics["loss"])
        with self._timers_lock:
            self.timers["train"] += time.perf_counter() - t0
        for s in staleness_hist:
            self._m_staleness.observe(s)
        rec = StepRecord(
            step=self.model_version,
            mean_reward=float(np.mean(batch["_rewards"])),
            loss=loss,
            mean_is_ratio=float(metrics.get("mean_is_ratio", 1.0)),
            staleness_hist=staleness_hist,
            wall_time=time.perf_counter(),
        )
        with self._history_lock:
            self.history.append(rec)
        return rec

    def _batch_from_trajs(self, trajs) -> Optional[Dict[str, Any]]:
        trajs = [t for t in trajs if t is not None and t.response]
        if not trajs:
            return None
        max_t = max(t.length for t in trajs)
        b = len(trajs)
        tokens = np.zeros((b, max_t), np.int32)
        blp = np.zeros((b, max_t), np.float32)
        mask = np.zeros((b, max_t), np.float32)
        groups, rewards = [], []
        for i, t in enumerate(trajs):
            seq = list(t.prompt) + list(t.response)
            tokens[i, : len(seq)] = seq
            plen = len(t.prompt)
            for j, lp in enumerate(t.behavior_logprobs):
                if plen + j < max_t:
                    blp[i, plen + j] = lp
                    mask[i, plen + j] = 1.0
            groups.append(t.group_id)
            rewards.append(t.reward or 0.0)
        return {
            "tokens": jnp.asarray(tokens),
            "behavior_logprobs": jnp.asarray(blp),
            "mask": jnp.asarray(mask),
            "advantages": jnp.asarray(group_advantages(rewards, groups)),
            "_rewards": rewards,
        }

    # --------------------------------------------------------- fault/elastic
    def fail_instance(self, inst_id: int) -> List[int]:
        """Simulate a replica failure. Returns trajectory IDs returned to TS.

        Safe mid-decode under the threaded scheduler: the handle leaves the
        fleet first (its thread exits at the next loop check), then its
        final state is read under its lock and every still-generating
        resident re-enters the TS via INTERRUPTED events; protocol
        reservations survive untouched.
        """
        with self.coordinator.lock:
            with self._instances_lock:
                handle = self.instances.pop(inst_id)
            with handle.lock:
                # dead first: a decode thread that already fetched this
                # handle must not generate on reclaimed trajectories when
                # it resumes stepping after we release the lock
                handle.retire()
                snap = handle.snapshot()
                resident = sorted(snap.run_trajs) + sorted(snap.wait_trajs)
                for tid in resident:
                    traj = self.ts.get(tid)
                    if traj is not None:
                        # INTERRUPTED clears the dead-instance affinity and
                        # the RUNNING status via the TS subscriber
                        self.lifecycle.interrupted(traj)
            # speculative state must forget the dead instance
            self.coordinator.drop_instance(inst_id)
            return resident

    def add_instance(self, inst_id: int) -> None:
        handle = self._new_instance(inst_id)
        with self.coordinator.lock:
            with self._instances_lock:
                self.instances[inst_id] = handle
            self.coordinator.spec.resync({inst_id: handle.snapshot()})

    # --------------------------------------------------------- observability
    _ENGINE_COUNTERS = (
        "decode_steps",
        "prefill_tokens",
        "reprefill_tokens",
        "decode_device_calls",
        "decode_tokens",
        "preemptions",
        "shared_prefix_hits",
        "prefill_tokens_saved",
        "block_copies",
    )

    def scrape_metrics(self) -> None:
        """Mirror the scattered component counters into the registry.

        The plain Python counters stay the source of truth (the engine's
        ``preemptions`` even feeds the coordinator's routing penalty);
        this just projects them onto the registry so one ``snapshot()``
        sees the whole fleet. Called by the FleetSampler each tick and
        by ``export_trace`` — a no-op when observability is off.
        """
        m = self.metrics
        if not m.enabled:
            return
        with self._instances_lock:
            handles = dict(self.instances)
        for inst_id, h in sorted(handles.items()):
            for name in self._ENGINE_COUNTERS:
                v = getattr(h, name, None)
                if v is not None:
                    m.counter(f"engine_{name}", instance=inst_id).set_total(v)
        st = self.coordinator.stats
        m.counter("coordinator_cycles").set_total(st.cycles)
        m.counter("coordinator_snapshots_rejected").set_total(
            st.snapshots_rejected
        )
        for kind, n in st.commands.items():
            m.counter("coordinator_commands", kind=kind).set_total(n)
        m.counter("coordinator_stream_cycles").set_total(st.stream_cycles)
        m.counter("coordinator_stream_routes").set_total(st.stream_routes)
        m.counter("coordinator_stream_rejected").set_total(st.stream_rejected)
        m.counter("ps_pushes").set_total(self.ps.push_count)
        m.counter("ps_pulls").set_total(self.ps.pull_count)
        for name, v in self.reward_server.stats().items():
            if isinstance(v, bool):
                continue
            m.gauge(f"reward_{name}").set(v)
        if self.reward_hub is not None:
            hs = self.reward_hub.stats()
            m.counter("reward_hub_unrouted").set_total(hs["unrouted"])
            for tag, rs in hs["routes"].items():
                for k in ("calls", "failures", "fallbacks", "aborts"):
                    m.counter(
                        f"reward_route_{k}", route=tag
                    ).set_total(rs[k])
                inner = rs.get("inner") or {}
                for k in ("retries", "timeouts", "kills"):
                    if k in inner:
                        m.counter(
                            f"reward_route_{k}", route=tag
                        ).set_total(inner[k])
                if "breaker_state" in inner:
                    m.gauge("reward_route_breaker_open", route=tag).set(
                        0.0 if inner["breaker_state"] == "closed" else 1.0
                    )
        for kind, n in self.lifecycle.counts.items():
            m.counter("lifecycle_events", kind=kind.name.lower()).set_total(n)
        m.gauge("model_version").set(self.model_version)
        m.gauge("staleness_in_flight").set(self.manager.in_flight())
        with self._timers_lock:
            timers = dict(self.timers)
        for name, v in timers.items():
            m.gauge(f"timer_{name}_s").set(v)
        sched = getattr(self, "scheduler", None)
        busy = getattr(sched, "busy", None)
        if busy is not None:
            lock = getattr(sched, "_busy_lock", None)
            if lock is not None:
                with lock:
                    busy = dict(busy)
            for name, v in busy.items():
                m.gauge("sched_busy_s", thread=name).set(v)
        # lock-order witness (when it ran): counters + one tracer activity
        # per violation, carrying the offending stack into the trace
        w = lock_witness.current()
        if w is not None:
            viol = w.violations()
            m.counter("lock_witness_acquires").set_total(w.acquires)
            m.counter("lock_witness_emits").set_total(w.emits)
            m.counter("lock_witness_edges").set_total(len(w.edges()))
            m.counter("lock_witness_order_violations").set_total(
                viol["order"]
            )
            m.counter("lock_witness_emit_under_lock").set_total(
                viol["emit_under_lock"]
            )
            m.counter("lock_witness_cycles").set_total(viol["cycles"])
            if self.tracer is not None:
                samples = w.order_violations + w.emit_under_lock
                for s in samples[self._witness_exported:]:
                    args = {k: v for k, v in s.items() if k != "stack"}
                    args["stack"] = "".join(s.get("stack", [])[-4:])
                    with span("lock_witness_violation", self.tracer, **args):
                        pass
                self._witness_exported = len(samples)

    def export_trace(self, path: Optional[str] = None) -> Optional[dict]:
        """Final metrics scrape + Chrome-trace export (None when off)."""
        if self.tracer is None:
            return None
        from repro.obs.export import export_chrome_trace

        self.scrape_metrics()
        return export_chrome_trace(self.tracer, path)

    # ------------------------------------------------------------ checkpoint
    def checkpoint(self, directory: str) -> str:
        # host copies taken under the trainer lock: a threaded trainer's
        # next step donates (frees) the optimizer state on the device
        with self._train_lock:
            params, opt_state = jax.device_get((self.params, self.opt_state))
        return ckpt_lib.save_checkpoint(
            directory,
            self.model_version,
            params,
            opt_state,
            extra_meta={"model_version": self.model_version, "tick": self._tick},
            protocol_state=ckpt_lib.dump_service_state(
                self.manager,
                reward_server=self.reward_server,
                retired=self.retired,
                lifecycle=self.lifecycle,
            ),
        )

    def restore(self, directory: str) -> None:
        params, opt, meta = ckpt_lib.restore_checkpoint(
            directory, self.params, self.opt_state
        )
        self.params = jax.tree_util.tree_map(jnp.asarray, params)
        self.opt_state = jax.tree_util.tree_map(jnp.asarray, opt)
        self.model_version = meta["extra"]["model_version"]
        self.manager, _services = ckpt_lib.load_service_state(meta["protocol"])
        self.coordinator.manager = self.manager
        self.coordinator.verifier.manager = self.manager
        # In-flight payloads (TS / rollout slots / reward queue) died with
        # the old process; their protocol entries would leave buffers Stuck
        # forever. Abort them — the work is simply re-generated, and the
        # staleness bound is unaffected (fresh trajectories get fresh
        # reservations). Consumed history is preserved.
        for key in self.manager.tracked_keys():
            self.manager.abort(key)
        self.retired.clear()
        self.manager.check_invariants()
        self.ps.push(self.params, self.model_version)
        with self._instances_lock:
            handles = dict(self.instances)
        for h in handles.values():
            h.pull(self.params, self.model_version)
        self.coordinator.spec.resync(self._snapshots())
