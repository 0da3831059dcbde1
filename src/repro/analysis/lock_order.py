"""Declared lock partial order for the runtime.

This registry is the single source of truth consumed by both the static
lint (:mod:`repro.analysis.lint`, rule RPL002) and the runtime witness
(:mod:`repro.analysis.witness`). A thread may acquire lock *B* while
holding lock *A* only if ``can_acquire(A, B)`` — i.e. B's rank is
strictly greater than A's, or A and B are the same order-keyed lock
class acquired in increasing key order (the sorted per-instance barrier
acquisition in ``RuntimeCore.coordinator_cycle``).

Rank bands (gaps left for future locks):

* 0–29   coordination roots: coordinator, instances registry, instance
* 30–49  domain state: trajectory server, staleness, group book,
         reward hub / breaker / verifier internals, retired store
* 50–69  event plane: lifecycle subscriber table, tracer, metrics
         registry
* 70–89  terminal leaves: per-instrument metric locks, ring stats,
         scheduler busy map, timers, history
* 90+    condition locks (EventGate, ReadWriteLock) — always leaves

Names in :data:`TERMINAL` are hard leaves: *nothing* may be acquired
while one is held, regardless of rank.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

#: name -> rank. Lower rank = acquired earlier (outermost).
RANKS: Dict[str, int] = {
    # coordination roots
    "coordinator": 0,
    "instances": 10,
    "instance": 20,  # per-backend LockedBackend.lock, order-keyed by inst_id
    # domain state
    "ts": 30,  # trajectory server table
    "staleness": 32,
    "groupbook": 34,
    "hub": 40,  # reward hub routing table
    "route": 41,  # per-route telemetry counters
    "breaker": 42,
    "retry": 43,
    "http": 43,
    "judge": 44,
    "sandbox": 44,
    "faults": 44,
    "reward": 46,  # RewardServer queue/accounting
    "retired": 48,
    # event plane
    "lifecycle": 50,  # subscriber table only; never held across dispatch
    "tracer": 60,
    "metrics": 62,  # MetricsRegistry instrument table
    # terminal leaves
    "metric": 70,  # individual Counter/Gauge/Histogram
    "stats": 70,  # Ring buffers
    "busy": 70,
    "timers": 70,
    "history": 70,
    "trainer": 70,  # the train step's donated optimizer state
    # condition locks
    "gate": 90,  # EventGate
    "ps": 90,  # ReadWriteLock (parameter server)
}

#: Lock classes where several same-named locks exist and nesting among
#: them is legal in strictly increasing ``order_key`` (inst_id) order.
ORDER_KEYED = frozenset({"instance"})

#: Hard leaves: nothing may be acquired while one of these is held.
TERMINAL = frozenset(
    {"metric", "stats", "busy", "timers", "history", "trainer", "gate", "ps"}
)

#: Condition-lock names (RPL005: notify must hold exactly its own lock).
CONDITIONS = frozenset({"gate", "ps"})

#: Locks under which lifecycle emission is tolerated. The coordinator /
#: fleet prefix of the order is emit-safe *by construction*: every
#: lifecycle subscriber that takes a lock takes the coordinator lock (or
#: something below it), and the coordinator lock is reentrant — so a
#: dispatch from inside this prefix can never invert the order. Emitting
#: under any *other* lock (a leaf, a reward/server lock, or the bus's
#: own subscriber-table lock) is the PR 5 deadlock shape and is flagged
#: by RPL001 / the witness.
EMIT_SAFE = frozenset({"coordinator", "instances", "instance"})

#: Modules whose attributes are touched from >= 2 thread roles
#: (coordinator loop, decode loops, reward workers, trainer, pusher,
#: obs samplers). Bare ``threading.Lock()`` attributes here must go
#: through the witness-aware factory (RPL003 facet A), and shared
#: containers must be mutated under a lock (facet B). Keys are path
#: suffixes; values name the roles for diagnostics.
MODULE_ROLES: Dict[str, Tuple[str, ...]] = {
    "runtime/core.py": ("coordinator", "decode", "trainer", "obs"),
    "runtime/schedulers.py": ("coordinator", "decode", "trainer"),
    "core/lifecycle.py": ("coordinator", "decode", "reward", "trainer"),
    "core/coordinator.py": ("coordinator", "reward", "trainer"),
    "core/reward_server.py": ("coordinator", "reward"),
    "core/parameter_server.py": ("trainer", "decode", "pusher"),
    "core/staleness.py": ("coordinator", "trainer"),
    "core/trajectory_server.py": ("coordinator", "reward", "trainer"),
    "obs/metrics.py": ("coordinator", "decode", "reward", "obs"),
    "obs/tracer.py": ("coordinator", "decode", "reward", "obs"),
    "obs/stats.py": ("coordinator", "obs"),
    "reward/hub.py": ("reward",),
    "reward/retry.py": ("reward",),
    "reward/faults.py": ("reward",),
    "reward/stub_judge.py": ("reward",),
    "reward/sandbox.py": ("reward",),
    "reward/http_verifier.py": ("reward",),
}

#: Seed-deterministic modules (RPL004): wall-clock reads and unseeded
#: PRNG draws here would break tick/seed reproducibility. Path suffixes.
DETERMINISTIC_MODULES: Tuple[str, ...] = (
    "sim/engine.py",
    "sim/baselines.py",
    "sim/workload.py",
    "runtime/schedulers.py",  # tick scheduler seed path
    "kernels/",
    "reward/faults.py",  # FaultSchedule: pure function of (seed, i)
    "rollout/sampler.py",
)

#: Source-pattern hints mapping lock *expressions* to declared names,
#: for cross-module references the lint cannot resolve from a factory
#: call in the same file (e.g. ``self.coordinator.lock`` seen from
#: runtime/core.py). Checked in order; first match wins. Patterns are
#: regexes applied to the unparsed expression source.
ATTR_HINTS: Tuple[Tuple[str, str], ...] = (
    (r"(^|\.)coordinator\.lock$", "coordinator"),
    (r"_instances_lock$", "instances"),
    (r"^(h|handle|inst|backend)\.lock$", "instance"),
    (r"_busy_lock$", "busy"),
    (r"_timers_lock$", "timers"),
    (r"_history_lock$", "history"),
)


def rank(name: str) -> Optional[int]:
    """Rank of a declared lock name, or None if unknown."""
    return RANKS.get(name)


def can_acquire(
    held: str,
    new: str,
    *,
    held_key: Optional[int] = None,
    new_key: Optional[int] = None,
) -> bool:
    """May a thread holding ``held`` acquire ``new``?

    Unknown names are permissive (the caller should skip them); the
    lint and witness only enforce between *declared* locks.
    """
    rh, rn = RANKS.get(held), RANKS.get(new)
    if rh is None or rn is None:
        return True
    if held in TERMINAL:
        return False
    if held == new and held in ORDER_KEYED:
        if held_key is None or new_key is None:
            return True  # keys unknown -> witness checks at runtime
        return new_key > held_key
    return rn > rh


def is_deterministic_module(path: str) -> bool:
    """True if ``path`` falls under a seed-deterministic module."""
    p = path.replace("\\", "/")
    for suffix in DETERMINISTIC_MODULES:
        if suffix.endswith("/"):
            if ("/" + suffix) in ("/" + p) or p.startswith(suffix):
                return True
        elif p.endswith(suffix):
            return True
    return False


def module_roles(path: str) -> Tuple[str, ...]:
    """Declared thread roles for ``path`` (empty if single-role)."""
    p = path.replace("\\", "/")
    for suffix, roles in MODULE_ROLES.items():
        if p.endswith(suffix):
            return roles
    return ()
