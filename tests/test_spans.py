"""Program spans (``repro.obs.spans``), the engine's re-prefill counter,
and the fixed names of the rollout programs.

A span always goes to the profiler's trace (a ``TraceAnnotation``), and to
a ``TrajectoryTracer`` when one is passed, under the same name and
arguments. The runtime tests drive one tiny paged tick job, shared by the
module: its first RL step runs with a lowering listener attached, and a
later one under ``jax.profiler.trace``.
"""
import glob
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.types import Trajectory, reset_traj_ids
from repro.models import model as M
from repro.obs import TrajectoryTracer, span
from repro.rollout.engine import RolloutInstance

CFG = get_arch("qwen2-1.5b").reduced()
PARAMS = M.init_params(CFG, jax.random.PRNGKey(0))

ENGINE_SPANS = {
    "engine.step", "engine.blocks", "engine.record", "engine.admit",
    "decode.dispatch", "decode.sync", "prefill.dispatch", "prefill.sync",
}
RUNTIME_SPANS = {
    "decode[0]", "cycle", "coordinator.decide", "coordinator.execute",
    "train_step", "trainer.batch", "trainer.dispatch", "trainer.push",
    "trainer.sync",
}


# ------------------------------------------------------------ the helper
def test_span_records_a_tracer_activity_under_its_name():
    tracer = TrajectoryTracer()
    with span("engine.step", tracer, inst=3):
        with span("decode.sync", tracer, rows=2):
            pass
    inner, outer = tracer.activities
    assert (outer.name, outer.args) == ("engine.step", {"inst": 3})
    assert (inner.name, inner.args) == ("decode.sync", {"rows": 2})
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert outer.track == threading.current_thread().name


def test_span_without_a_tracer_records_nothing():
    tracer = TrajectoryTracer()
    with span("engine.step", inst=0):
        pass
    with span("trainer.sync", None):
        pass
    assert list(tracer.activities) == []


def test_span_closes_when_its_body_raises():
    tracer = TrajectoryTracer()
    with pytest.raises(ValueError):
        with span("trainer.dispatch", tracer):
            raise ValueError("step failed")
    assert [a.name for a in tracer.activities] == ["trainer.dispatch"]


# ---------------------------------------------------- re-prefill counter
def _traj(tid, prompt_len, max_new):
    prompt = list(np.random.RandomState(tid).randint(3, 17, size=prompt_len))
    return Trajectory(traj_id=tid, prompt=prompt, group_id=-1,
                      max_new_tokens=max_new)


def test_reprefill_counts_readmission_after_interrupt_and_pull():
    inst = RolloutInstance(0, CFG, PARAMS, 0, max_slots=2, max_len=64,
                           temperature=0.0, paged=True, kv_block_size=8)
    t = _traj(1, prompt_len=6, max_new=16)
    inst.route(t)                  # prefill: 6 prompt tokens, 1 sampled
    for _ in range(3):
        inst.step()                # 3 more tokens
    assert (inst.prefill_tokens, inst.reprefill_tokens) == (6, 0)
    inst.interrupt([t.traj_id])
    inst.pull(PARAMS, 1)
    assert t.n_generated == 4
    inst.route(t)                  # forwards prompt + partial response
    assert inst.reprefill_tokens == 6 + 4
    assert inst.prefill_tokens == 6 + 10


def test_reprefill_counts_readmission_after_preemption():
    # 9 blocks of 8 tokens for three trajectories growing to ~35 tokens
    # each: the pool runs out mid-decode and preempts the youngest
    inst = RolloutInstance(0, CFG, PARAMS, 0, max_slots=3, max_len=64,
                           temperature=0.0, eos_id=-1, paged=True,
                           kv_block_size=8, kv_pool_blocks=9)
    trajs = {t.traj_id: t for t in
             (_traj(800 + i, prompt_len=5 + i, max_new=30) for i in range(3))}
    preempted, expected = set(), 0

    def on_admit(inst_id, ids):
        nonlocal expected
        for tid in ids:
            if tid in preempted:
                # prompt + the response it had; admission sampled one more
                t = trajs[tid]
                expected += len(t.prompt) + t.n_generated - 1

    inst.on_admit = on_admit
    inst.on_preempt = lambda inst_id, tid: preempted.add(tid)
    for t in trajs.values():
        inst.route(t)
    done = []
    for _ in range(400):
        done.extend(inst.step())
        if len(done) == 3:
            break
    assert len(done) == 3 and inst.preemptions > 0
    assert inst.reprefill_tokens == expected > 0
    # on the prefill_tokens basis: the rest is each prompt's first prefill
    assert inst.prefill_tokens - inst.reprefill_tokens == 5 + 6 + 7


def test_sim_backend_counts_reprefill():
    from repro.core.cost_model import PAPER_H20_QWEN3_30B
    from repro.rollout.backend import SimBackend

    sim = SimBackend(0, PAPER_H20_QWEN3_30B)
    fresh, resumed = _traj(1, 10, 8), _traj(2, 10, 8)
    resumed.sim_generated = 5
    sim.route(fresh, 0.0)
    sim.route(resumed, 0.0)
    assert sim.prefill_tokens == 10 + 15
    assert sim.reprefill_tokens == 15


# ------------------------------------------------------ a tiny tick job
def _one_step(rt):
    v = rt.model_version
    for _ in range(2000):
        rt.tick()
        if rt.model_version != v:
            return
    raise AssertionError("no training step in 2000 ticks")


@pytest.fixture(scope="module")
def job():
    """A tick job with the tracer on, after its first RL step, and the
    names of the programs that step lowered."""
    from jax._src import dispatch

    from repro.runtime.async_runtime import AsyncRLRuntime, RuntimeConfig

    lowered = []

    def on_duration(event, duration, **kw):
        # a program lowers as "jit(<function name>)"
        if event == dispatch.JAXPR_TO_MLIR_MODULE_EVENT:
            lowered.append(str(kw.get("fun_name")))

    reset_traj_ids()
    rt = AsyncRLRuntime(CFG, RuntimeConfig(
        eta=1, batch_size=1, group_size=2, n_instances=1, max_slots=4,
        max_len=48, max_new_tokens=4, total_steps=1 << 20, seed=0,
        paged_kv=True, share_prefix=True, scheduler="tick",
        observability=True,
    ))
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        _one_step(rt)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    return rt, lowered


def test_rollout_programs_lower_under_fixed_names(job):
    _, lowered = job
    names = {"rollout_prefill", "rollout_paged_scatter",
             "rollout_prefill_sample", "rollout_decode"}
    assert {f"jit({n})" for n in names} <= set(lowered)
    # the paged decode step samples inside rollout_decode; the separate
    # sampler program is the dense path's only
    assert "jit(rollout_sample)" not in lowered
    # what the names replace: jit's defaults for a local step function
    # and a lambda
    assert not {"jit(step)", "jit(<lambda>)"} & set(lowered)
    # a module-level program is lowered once per process, perhaps by an
    # earlier test: lower it here
    from repro.rollout.sampler import stream_keys

    ids = jnp.zeros((2,), jnp.uint32)
    assert "@jit_stream_keys" in stream_keys.lower(
        jax.random.PRNGKey(0), ids, ids).as_text()


def test_tracer_holds_every_span_under_its_name(job):
    rt, _ = job
    names = {a.name for a in rt.tracer.activities}
    assert ENGINE_SPANS | RUNTIME_SPANS <= names
    step = [a for a in rt.tracer.activities if a.name == "engine.step"]
    assert all(a.args == {"inst": 0} for a in step)


def test_train_timer_ends_after_the_host_waits_on_the_step(job):
    rt, _ = job
    assert "prefill" not in rt.timers
    acts = rt.tracer.activities
    waited = sum(a.t1 - a.t0 for a in acts
                 if a.name in ("trainer.dispatch", "trainer.sync"))
    assert 0 < waited <= rt.timers["train"]


def test_profiler_trace_holds_the_program_spans(job, tmp_path):
    from jax.profiler import ProfileData

    rt, _ = job
    # the host tracer records the spans; the Python tracer (on by
    # default) would only slow the run
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        _one_step(rt)
        _one_step(rt)
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    assert len(files) == 1
    names = {
        e.name
        for plane in ProfileData.from_file(files[0]).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    }
    assert {"engine.step", "decode.sync", "trainer.dispatch"} <= names


def test_engine_tracer_follows_the_runtime():
    from repro.runtime.async_runtime import AsyncRLRuntime, RuntimeConfig

    reset_traj_ids()
    rt = AsyncRLRuntime(CFG, RuntimeConfig(
        eta=1, batch_size=1, group_size=2, n_instances=1, max_slots=2,
        max_len=48, max_new_tokens=4, seed=0, paged_kv=True,
    ))
    inst = rt.instances[0].inner
    assert rt.tracer is None and inst.tracer is None
    tracer = TrajectoryTracer()
    inst.tracer = tracer
    assert inst.prefill_runner.tracer is tracer
    assert inst.paged_decode_runner.tracer is tracer
