"""One run of one cell: build the job, warm it up, measure a window, check.

The entry the window drives is the program's own: ``AsyncRLRuntime`` with
the tick scheduler, stepped by ``rt.tick()`` (decode on every instance,
reward inline, the coordinator cycle, ``train_once``, the refill). The
harness adds, from outside:

* its traffic: prompts for every group, and each trajectory's output
  budget, set on the trajectory's first ``ROUTED`` event (the engine reads
  it at admission; the coordinator never sees it);
* lifecycle subscribers that time each trajectory from its first
  ``ROUTED`` to ``REWARDED`` and count tokens as the trainer consumes them;
* host spans (``jax.profiler.TraceAnnotation``) around the runtime's calls
  into each layer, for the traced run;
* a capture of the first training steps' batches and results, which the
  plain reference follows once the window has closed, scoring every
  trajectory those steps consumed.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import statistics
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench_trace"

# published configuration keys -> the program's ArchConfig fields. Applied
# in this order, so an expert model's ``moe_intermediate_size`` wins over
# its dense ``intermediate_size``. ``num_experts`` is left out: the router's
# width is published, the experts held here are the file's ``program``
# block's to say.
ARCH_FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "moe_intermediate_size": "d_ff",
    "num_experts_per_tok": "top_k",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "qkv_bias": "qkv_bias",
}
# training steps the reference follows
CHECK_STEPS = 3
# warm-up RL steps, the same number on every seed so that set-up does the
# same work. Admission waves of new sizes (rows, members, prompt bucket;
# each instance has its own programs) keep turning up for one to two dozen
# steps, then rarely; a warm-up that stopped at the first quiet stretch
# left a seed 15-24 programs to lower inside its window.
WARM_STEPS = 24
# traced RL steps in a --trace 1 run
TRACE_STEPS = 3


def load_config(name: str, directory: Path = BENCH / "configs"):
    """(the program's ArchConfig, the file's dict) for a configuration.

    The fields start from the program's entry ``repro_arch``, take the
    file's published keys through ``ARCH_FIELDS``, then its ``"program"``
    block, which names ArchConfig fields directly (what no published key
    names one-to-one). A ``program`` field ArchConfig lacks, and a missing
    or unknown ``reference`` model, are errors."""
    import dataclasses

    from chipbench import refmodels
    from repro.configs import get_arch

    conf = json.loads((directory / f"{name}.json").read_text())
    refmodels.load(conf)
    base = get_arch(conf["repro_arch"])
    program = conf.get("program", {})
    unknown = sorted(set(program) - {f.name for f in dataclasses.fields(base)})
    if unknown:
        raise ValueError(f"{name}: program fields {unknown} are not fields "
                         "of the program's ArchConfig")
    fields = {f: conf[k] for k, f in ARCH_FIELDS.items() if k in conf}
    return dataclasses.replace(base, **{**fields, **program}), conf


def load_limits(workload: str) -> Dict[str, float]:
    path = BENCH / "limits" / f"{workload}.json"
    return {k: float(v["limit"]) for k, v in json.loads(path.read_text()).items()}


def enable_compile_cache() -> str:
    """JAX's persistent cache in the checkout, at a fixed path, keeping
    every program however quick its compile (the default keeps only those
    over a second, and every run would compile the rest again). The TPU
    runtime's own logs would go to a fixed directory under ``/tmp``; they
    are turned off unless the environment names a directory."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class LoweringCounter:
    """Counts programs lowered, programs fetched for the backend (a
    persistent cache hit or a compile), and persistent cache hits; keeps
    the names of the programs lowered."""

    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.lowered = 0
        self.compiled = 0
        self.cache_hits = 0
        self.names: List[str] = []

    def __enter__(self):
        import jax
        from jax._src import dispatch

        self._lower = dispatch.JAXPR_TO_MLIR_MODULE_EVENT
        self._compile = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == self._lower:
            self.lowered += 1
            self.names.append(str(kw.get("fun_name", "?")))
        elif event == self._compile:
            self.compiled += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.CACHE_HIT:
            self.cache_hits += 1


@dataclass
class Ledger:
    """Per-trajectory lifecycle times and per-step consumed tokens."""

    clock: Callable[[], float] = time.perf_counter
    routed_at: Dict[int, float] = field(default_factory=dict)
    rewarded_at: Dict[int, float] = field(default_factory=dict)
    sizes: Dict[int, tuple] = field(default_factory=dict)  # (group, prompt, response)
    terminal: Dict[int, int] = field(default_factory=dict)
    aborted: set = field(default_factory=set)
    consumed: List[int] = field(default_factory=list)  # since the last boundary
    rewarded_twice: int = 0

    def on_routed(self, e) -> None:
        self.routed_at.setdefault(e.traj_id, self.clock())

    def on_rewarded(self, e) -> None:
        if e.traj_id in self.rewarded_at:
            self.rewarded_twice += 1
        self.rewarded_at[e.traj_id] = self.clock()
        t = e.traj
        self.sizes[t.traj_id] = (t.group_id, len(t.prompt), len(t.response))

    def on_consumed(self, e) -> None:
        self.terminal[e.traj_id] = self.terminal.get(e.traj_id, 0) + 1
        self.consumed.append(e.traj_id)

    def on_aborted(self, e) -> None:
        self.terminal[e.traj_id] = self.terminal.get(e.traj_id, 0) + 1
        self.aborted.add(e.traj_id)

    def take_consumed(self):
        """(response tokens, [(prompt length, [response lengths])] per
        group) consumed since the last call."""
        ids, self.consumed = self.consumed, []
        groups: Dict[int, tuple] = {}
        tokens = 0
        for i in ids:
            g, p, r = self.sizes[i]
            tokens += r
            groups.setdefault(g, (p, []))[1].append(r)
        return tokens, list(groups.values())

    def violations(self) -> int:
        """Trajectories with more than one terminal event, or rewarded
        twice; every routed trajectory has to end in exactly one."""
        twice = sum(1 for n in self.terminal.values() if n != 1)
        return twice + self.rewarded_twice


def install_traffic(rt, traffic, ledger: Ledger) -> None:
    """Point the job at the harness's traffic before its first tick.

    ``RuntimeCore.__init__`` has already filled the trajectory server from
    its own dataset: those groups get the harness's prompts, and the
    server's prompt source becomes the harness's generator. The harness
    reaches into private state for this and for the engines' EOS id (the
    program has no prompt or stop hook)."""
    from repro.core.lifecycle import LifecycleEventKind as K

    ts = rt.ts
    for gid in sorted(ts.groups):
        prompt = traffic.prompt(gid)
        for tid in ts.groups[gid].traj_ids:
            t = ts.registry[tid]
            t.prompt = list(prompt)
            t._prompt_key = None
    ts._source = traffic.prompts(ts._group_counter)
    budgeted: set = set()

    def on_routed(e) -> None:
        if e.traj_id in budgeted:
            return
        budgeted.add(e.traj_id)
        t = e.traj
        member = ts.groups[t.group_id].traj_ids.index(t.traj_id)
        t.max_new_tokens = traffic.budget(t.group_id, member)

    # the budget alone ends a response: random weights sample the
    # tokenizer's EOS id now and then (about once in 150k tokens at
    # qwen2's vocabulary), which would cut a response short, change the
    # run's work by its seed and give a train batch a length no warm-up
    # covered
    for h in rt.instances.values():
        h.inner.eos_id = -1
    rt.lifecycle.subscribe(K.ROUTED, on_routed)
    rt.lifecycle.subscribe(K.ROUTED, ledger.on_routed)
    rt.lifecycle.subscribe(K.REWARDED, ledger.on_rewarded)
    rt.lifecycle.subscribe(K.CONSUMED, ledger.on_consumed)
    rt.lifecycle.subscribe(K.ABORTED, ledger.on_aborted)


class Capture:
    """Records what the job's first ``CHECK_STEPS`` training steps consumed
    and produced, for the reference to follow after the window.

    Wraps ``rt.train_step`` (the jitted step) until the last captured step,
    then puts the program's own callable back."""

    def __init__(self, rt, fault: Optional[str] = None):
        self.rt = rt
        self.fault = fault
        self.batches: List[List[Dict[str, Any]]] = []
        self.losses: List[float] = []
        self.ratios: List[float] = []
        self.grad_norms: Dict[str, float] = {}
        self.change_norms: Dict[str, float] = {}
        self.seconds = 0.0
        # the version-0 weights: a reference to the immutable arrays, not a
        # copy; the norms below are taken on the device
        self._init = rt.params
        self._step = rt.train_step
        self._take = rt.retired.take
        rt.train_step = self._train_step
        rt.retired.take = self._take_batch

    @property
    def done(self) -> bool:
        return len(self.losses) >= CHECK_STEPS

    def _take_batch(self, ids):
        trajs = self._take(ids)
        if not self.done:
            self.batches.append(
                [_payload(t) for t in trajs if t is not None and t.response]
            )
        return trajs

    def _train_step(self, params, opt_state, batch):
        import jax
        import jax.numpy as jnp
        import numpy as np

        if self.fault == "half_batch":
            # half of the rows left out: the mean runs over the rest
            mask = np.asarray(batch["mask"]).copy()
            mask[mask.shape[0] // 2:] = 0.0
            batch = dict(batch, mask=jnp.asarray(mask))
        out = self._step(params, opt_state, batch)
        if self.fault == "frozen":
            # a step that hands back the state it was given
            out = (params, out[1], out[2])
        t0 = time.perf_counter()
        new_params, new_state, metrics = out
        self.losses.append(float(metrics["loss"]))
        self.ratios.append(float(metrics["mean_is_ratio"]))
        if len(self.losses) == 1:
            # AdamW's first moment after one step is (1 - b1) x the
            # clipped gradient the optimizer got
            self.grad_norms = _norms(new_state["m"], scale=1.0 / (1.0 - 0.9))
        if self.done:
            self.change_norms = _norms(
                jax.tree_util.tree_map(jnp.subtract, new_params, self._init)
            )
            self._init = None
            self.rt.train_step = self._step
            self.rt.retired.take = self._take
        self.seconds += time.perf_counter() - t0
        return out


def _payload(t) -> Dict[str, Any]:
    return {
        "id": t.traj_id, "group": t.group_id, "prompt": list(t.prompt),
        "response": list(t.response),
        "behavior_logprobs": list(t.behavior_logprobs),
        "segments": [tuple(s) for s in t.segments],
    }


def _norms(tree, scale: float = 1.0) -> Dict[str, float]:
    """Per-leaf norms, taken on the device (copying the leaves to the host
    would cost seconds per run); only the numbers come back."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    leaf_norms = jax.jit(lambda xs: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs])
    norms = jax.device_get(leaf_norms([x for _, x in flat]))
    return {jax.tree_util.keystr(p): float(n) * scale
            for (p, _), n in zip(flat, norms)}


def install_token_fault(rt) -> None:
    """A fault for the checks' own tests and calibration: every sampled
    token is recorded one id off, where the engine produces it."""
    for h in rt.instances.values():
        inst = h.inner
        record = inst._record_token
        vocab = inst.cfg.vocab_size

        def altered(traj, token, blp, record=record, vocab=vocab):
            return record(traj, (int(token) + 1) % vocab, blp)

        inst._record_token = altered


# ----------------------------------------------------------------- spans
SPANS = {
    # runtime method -> host span name (the layer it calls into)
    "decode_instance": "rollout",
    "coordinator_cycle": "coordinator",
    "train_once": "trainer",
}


def install_spans(rt) -> None:
    """Host spans around the runtime's calls into each layer, written into
    the profiler's trace (no cost while no trace is taken)."""
    import jax

    def wrap(fn, name):
        def spanned(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        return spanned

    for attr, name in SPANS.items():
        setattr(rt, attr, wrap(getattr(rt, attr), name))
    rt.ts.refill = wrap(rt.ts.refill, "refill")
    rt.complete_trajectory = wrap(rt.complete_trajectory, "reward")


class WorkLog:
    """Per-call sizes of the kernels the roofline metrics read, recorded
    while ``on`` (the traced steps)."""

    def __init__(self):
        self.on = False
        self.decode: List[List[int]] = []      # valid K/V rows per live row
        self.prefill: List[List[int]] = []     # tokens per full-prefill row
        self.suffix: List[List[tuple]] = []    # (suffix, total) per fork row

    def install(self, rt) -> None:
        for h in rt.instances.values():
            inst = h.inner
            dec = inst.paged_decode_runner
            pre = inst.prefill_runner
            dec.run = self._decode(dec.run)
            pre.run = self._prefill(pre.run)

    def _decode(self, fn):
        def run(*a, **kw):
            out = fn(*a, **kw)
            if self.on:
                self.decode.append([int(p) for p in out[2].positions])
            return out
        return run

    def _prefill(self, fn):
        def run(params, cache, jobs):
            if self.on:
                full = [len(j.tokens) for j in jobs if j.suffix_start is None]
                sfx = [(len(j.tokens) - j.suffix_start, len(j.tokens))
                       for j in jobs if j.suffix_start is not None]
                if full:
                    self.prefill.append(full)
                if sfx:
                    self.suffix.append(sfx)
            return fn(params, cache, jobs)
        return run


def counters(rt) -> Dict[str, float]:
    hs = list(rt.instances.values())
    out = {
        name: float(sum(getattr(h, name) for h in hs))
        for name in ("decode_steps", "decode_tokens", "prefill_tokens",
                     "prefill_tokens_saved", "shared_prefix_hits",
                     "block_copies", "preemptions")
    }
    out["slots"] = float(sum(h.max_slots for h in hs))
    for k, v in rt.timers.items():
        out[f"timer_{k}"] = v
    out["model_version"] = float(rt.model_version)
    return out


# --------------------------------------------------------------- warm-up
def warm_train(rt, traffic) -> None:
    """Compile the train step at every padded length ``T`` the cell's
    batches can take. Runs before the first real step, while the optimizer
    state is still all zeros: the step donates that state, so each call
    gets it back and passes it on, and the runtime ends up with fresh
    zeros, as its own initializer made them."""
    import jax
    import jax.numpy as jnp

    mix = traffic.mix
    b = mix.batch_size * mix.group_size
    state = rt.opt_state
    for t_len in traffic.train_lengths():
        batch = {
            "tokens": jnp.zeros((b, t_len), jnp.int32),
            "behavior_logprobs": jnp.zeros((b, t_len), jnp.float32),
            "mask": jnp.zeros((b, t_len), jnp.float32),
            "advantages": jnp.zeros((b,), jnp.float32),
            "_rewards": [0.0] * b,
        }
        _, state, metrics = rt.train_step(rt.params, state, batch)
        jax.block_until_ready(state)
        float(metrics["loss"])
    rt.opt_state = jax.tree_util.tree_map(jnp.zeros_like, state)
    jax.block_until_ready(rt.opt_state)


def warm_decode(rt) -> None:
    """Run every decode program shape (one per count of live slots) once,
    through each instance's own runner, on null block tables; the output
    cache is dropped, so the instance's pool is as it was."""
    import jax
    import jax.numpy as jnp

    from repro.rollout.sampler import stream_keys

    for h in rt.instances.values():
        inst = h.inner
        runner = inst.paged_decode_runner
        for n in range(1, inst.max_slots + 1):
            active = list(range(n))
            keys = stream_keys(
                inst._base_key,
                jnp.asarray(active, jnp.uint32),
                jnp.asarray([0] * n, jnp.uint32),
            )
            out = runner.run(inst.params, inst.cache, active,
                             {s: [] for s in active}, inst._last_tokens, keys)
            jax.block_until_ready(out[1])


# ------------------------------------------------------------------ a run
@dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, Dict[str, Any]]
    device: Dict[str, Any]
    checks: List[Dict[str, Any]]
    breakdown: Optional[Dict[str, Any]] = None
    info: Dict[str, Any] = field(default_factory=dict)


def run_cell(
    workload: Dict[str, Any],
    bench: Dict[str, Any],
    *,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    config_dir: Path = BENCH / "configs",
    traffic_dir: Optional[Path] = None,
    limits: Optional[Dict[str, float]] = None,
    fault: Optional[str] = None,
    control: bool = False,
    warm: bool = True,
    keep: Optional[Dict[str, Any]] = None,
    log: Callable[[str], None] = lambda s: None,
) -> Result:
    """One run of ``workload``.

    ``fault`` plants a fault in the timed path and ``control`` puts the
    control in the program's place (``check.compare``); both are for the
    checks' own tests and calibration, and must make ``correct`` false.
    ``warm=False`` (calibration only) skips warming the shapes and stops
    warm-up once the checked steps are in: the compared numbers come from
    the job's first training steps and the trajectories they consumed,
    which neither touches. ``keep`` receives the check's readings."""
    import jax

    from chipbench import check, metrics as metric_readers, traffic as T
    from repro.core.types import reset_traj_ids
    from repro.runtime.async_runtime import AsyncRLRuntime, RuntimeConfig

    cfg, conf = load_config(workload["config"], config_dir)
    mix = T.load_mix(workload["traffic"], traffic_dir or T.TRAFFIC_DIR)
    traffic = T.Traffic(mix, seed, cfg.vocab_size)
    if limits is None:
        limits = load_limits(workload["name"])
    rcfg = RuntimeConfig(
        eta=mix.eta, batch_size=mix.batch_size, group_size=mix.group_size,
        n_instances=mix.n_instances, max_slots=mix.max_slots,
        max_len=mix.max_len, max_new_tokens=mix.output_cap,
        total_steps=1 << 30, lr=mix.lr, temperature=mix.temperature,
        seed=check.weights_seed(seed), paged_kv=True, share_prefix=True,
        kv_block_size=mix.kv_block_size, scheduler="tick",
        decode_steps_per_tick=mix.decode_steps_per_tick,
        reward_fn=check.even_token_share,
    )
    device = jax.devices()[0]
    # trajectory ids (and the sampling streams keyed on them) start from 0,
    # as in a fresh process, however many runs a process makes
    reset_traj_ids()
    with LoweringCounter() as lowering:
        rt = AsyncRLRuntime(cfg, rcfg)
        ledger = Ledger()
        install_traffic(rt, traffic, ledger)
        if fault == "token":
            install_token_fault(rt)
        log(f"built: {time.perf_counter() - t_start:.2f}s")
        if warm:
            warm_train(rt, traffic)
            log(f"train warm: {time.perf_counter() - t_start:.2f}s "
                f"lowered={lowering.lowered} compiled={lowering.compiled} "
                f"cache hits={lowering.cache_hits}")
            warm_decode(rt)
            log(f"decode warm: {time.perf_counter() - t_start:.2f}s "
                f"lowered={lowering.lowered} compiled={lowering.compiled} "
                f"cache hits={lowering.cache_hits}")
        capture = Capture(rt, fault=fault)
        install_spans(rt)
        work = WorkLog()
        work.install(rt)
        # warm-up RL steps through the window's own call
        steps = WARM_STEPS if warm else CHECK_STEPS
        per_step = []
        for _ in range(steps):
            before = lowering.lowered
            _one_step(rt)
            per_step.append(lowering.lowered - before)
        jax.block_until_ready(rt.params)
        ledger.take_consumed()
        t0 = time.perf_counter()
        setup_s = t0 - t_start - capture.seconds
        log(f"warm: {steps} RL steps, lowered per step {per_step}, "
            f"setup_s={setup_s:.2f} compiled={lowering.compiled} "
            f"cache hits={lowering.cache_hits}")
        lowered0, compiled0 = lowering.lowered, lowering.compiled
        hits0 = lowering.cache_hits
        c0 = counters(rt)
        if trace:
            if TRACE_DIR.exists():
                shutil.rmtree(TRACE_DIR)
            work.on = True
            jax.profiler.start_trace(str(TRACE_DIR))
        boundaries = [(t0, 0, [])]
        v = rt.model_version
        while True:
            rt.tick()
            if rt.model_version != v:
                v = rt.model_version
                jax.block_until_ready(rt.params)
                now = time.perf_counter()
                if now - t0 > seconds and len(boundaries) > 1 and not trace:
                    break
                boundaries.append((now, *ledger.take_consumed()))
                if trace and len(boundaries) > TRACE_STEPS:
                    break
        if trace:
            jax.profiler.stop_trace()
            work.on = False
        c1 = counters(rt)
        window_lowered = lowering.lowered - lowered0
        window_compiled = lowering.compiled - compiled0
        window_hits = lowering.cache_hits - hits0
        window_names = lowering.names[lowered0:]
    t_end = boundaries[-1][0]
    window_s = t_end - t0
    steps_in_window = len(boundaries) - 1
    tokens = sum(n for _, n, _ in boundaries[1:])
    groups = [g for _, _, gs in boundaries[1:] for g in gs]
    lat = [
        ledger.rewarded_at[i] - ledger.routed_at[i]
        for i, t in ledger.rewarded_at.items() if t0 <= t <= t_end
    ]
    routed = [i for i, t in ledger.routed_at.items() if t0 <= t <= t_end]
    attempted = len(routed)
    failed = sum(1 for i in routed if i in ledger.aborted)
    stats = device.memory_stats() or {}
    device_info = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
    }
    staleness = rt.manager.max_consumed_staleness()
    info = {
        "setup_s": setup_s, "warm_steps": steps, "window_s": window_s,
        "window_steps": steps_in_window, "window_lowered": window_lowered,
        "window_compiled": window_compiled, "window_cache_hits": window_hits,
        "window_programs": window_names, "tokens": tokens,
        "rewarded": len(lat), "capture_s": capture.seconds,
        "counts": {k: c1[k] - c0[k] for k in (
            "decode_steps", "decode_tokens", "prefill_tokens",
            "preemptions", "block_copies")},
    }
    log(f"window: {json.dumps(info)}")
    ctx = metric_readers.Context(
        cfg=cfg, conf=conf, mix=mix, window_s=window_s,
        steps=steps_in_window, c0=c0, c1=c1, work=work,
        lowered=window_lowered, groups=groups,
        device_kind=device.device_kind,
    )
    breakdown = None
    if trace:
        from chipbench import trace as trace_reader

        ctx.trace = trace_reader.read(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device_info["busy_s"] = ctx.trace.busy_s
        device_info["window_s"] = ctx.trace.window_s
        breakdown = ctx.trace.breakdown()
    # the program's state goes before the reference runs
    violations = ledger.violations()
    eta = rcfg.eta
    capture.rt = None
    del rt
    gc.collect()
    log(f"live device bytes before the reference: "
        f"{sum(a.nbytes for a in jax.live_arrays())}")
    t_ref = time.perf_counter()
    checks = check.compare(conf, seed, mix, capture, limits,
                           staleness=staleness, eta=eta,
                           violations=violations, control=control,
                           keep=keep)
    log(f"reference: {time.perf_counter() - t_ref:.2f}s")
    correct = all(c["ok"] for c in checks)
    if trace:
        values = metric_readers.read_all(bench, workload["name"], ctx)
    else:
        values = {
            "rl_tokens_per_s": (tokens / window_s, "tokens/s"),
            "traj_p95_s": (_p95(lat), "s"),
            "setup_s": (setup_s, "s"),
        }
    metrics = {k: {"value": v, "unit": u} for k, (v, u) in values.items()
               if v is not None}
    return Result(correct, attempted, failed, metrics, device_info, checks,
                  breakdown, info)


def _one_step(rt, max_ticks: int = 100_000) -> None:
    """Tick until the trainer lands a step."""
    v = rt.model_version
    for _ in range(max_ticks):
        rt.tick()
        if rt.model_version != v:
            return
    raise RuntimeError(f"no training step in {max_ticks} ticks")


def _p95(values: List[float]) -> Optional[float]:
    if len(values) < 20:
        return None
    return statistics.quantiles(values, n=20, method="inclusive")[18]
