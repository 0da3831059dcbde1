"""Drive the asynchronous RL loop once on a TPU and check what comes out.

    python chip_smoke.py                 # one chip (the default)
    python chip_smoke.py --four-chips    # the sharded rollout path, 4 chips

One chip: ``AsyncRLRuntime`` -- two paged, prefix-sharing rollout instances
on the tick scheduler, the reward phase, and the DAPO trainer under the
eta=1 staleness protocol -- at qwen2-1.5b's published widths with random
weights from ``--seed``. Depth, batch and length are cut to fit one chip
(see ``LOOP`` below). The run fails unless every requested step lands,
every loss and IS ratio is finite, realized staleness stays within eta, a
Pull follows a weight update, and the rollout's behavior logprobs agree
with a float32 forward of the same tokens under the same weights.

Four chips: one ``ShardedBackend`` (paged K/V pool head-sharded over a
4-device ``("tensor",)`` mesh) against the single-device paged engine on
the same seeded prompts, in the same process, at granite-3-8b widths.

Everything runs in this one process: the chip belongs to one process at a
time. The script refuses to run without a TPU; it never falls back to the
CPU. Its last line of standard output is one JSON object naming the
device; every earlier line is a human-readable measurement or check.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# --------------------------------------------------------------- one chip
# qwen2-1.5b in float32 is 0.934 GB of (tied) embedding plus 0.187 GB per
# layer. The runtime keeps AdamW m and v, gradients during the step, and up
# to four parameter versions at once (the train step's output, the
# parameter server's copy, and one per rollout instance, each up to eta=1
# behind; params are not donated because those holders share them): seven
# copies, 6.5 + 1.31 * layers GB. At 4 layers that is 11.8 GB, plus 0.83 GB
# of train-step scratch at batch 8 x 32 and 0.27 GB of prefill/decode
# scratch (memory_analysis of the described-v5e compile) = 12.9 GB of the
# 15.75 GB the compiler allows. A fifth layer (14.2 GB) leaves too little
# for the allocator; all 28 layers would need 43 GB. Batch (4 groups of 2
# = 8 trajectories per step) and length (prompts <= 8 tokens + 24 new,
# so <= 32) keep the float32 logits over the 152,064-column vocabulary,
# which dominate the train step's scratch, well inside that budget.
LOOP = dict(
    arch="qwen2-1.5b", n_layers=4, batch_size=4, group_size=2,
    n_instances=2, max_slots=8, max_len=64, max_new_tokens=24,
    total_steps=3, eta=1, kv_block_size=16, lr=1e-5,
)
# Rollout behavior logprobs against a float32 ("highest" precision) XLA
# forward of the same tokens under the same version-0 weights. The chip's
# default matmul precision rounds matmul inputs to bfloat16; rounding just
# the weights moved qwen2-1.5b-width logprobs by 0.006 on average (0.024
# at most) on the CPU, and the chip run at seed 0 measured 0.0084 (0.031
# at most). The bounds are about 3.5x and 5x that, while a wrong block
# table, position or copy-on-write block moves logprobs by 0.3 (a position
# off by one) to 1 (a wrong context) on average.
REF_MEAN_ATOL = 0.03
REF_MAX_ATOL = 0.15
REF_TRAJS = 8

# ------------------------------------------------------------- four chips
# granite-3-8b has 8 KV heads, so its paged pool shards 4 ways. Each
# sharded step gathers the whole weights onto every device
# (ctx.gather_params): 4 layers are 4.0 GB of float32 weights, of which
# each device stores 2.4 GB (the rest is gathered inside the step). The
# reference engine keeps another 4.0 GB on device 0, which so holds about
# 8 GB of weights plus 1.6 GB of step scratch (memory_analysis of the
# described 2x2 v5e compile). All 40 layers would be 33 GB gathered alone.
SHARDED = dict(
    arch="granite-3-8b", n_layers=4, shards=4, max_slots=4, max_len=64,
    max_new_tokens=24, kv_block_size=16, kv_pool_blocks=9,
)
# Both engines run the same jnp reference kernels at the same matmul
# precision; they differ only in how XLA partitions and orders float32
# accumulations (~1e-6 relative per reduction), which stays far below
# this bound through 4 layers and a 49k-way logsumexp (four v5e chips at
# seed 0 measured 0: bitwise equal). A wrong head shard or block table
# moves logprobs by 0.3-1 (see REF_MEAN_ATOL above).
SHARDED_ATOL = 1e-3


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def _check(ok: bool, msg: str) -> None:
    print(f"check: {msg}: {'ok' if ok else 'FAILED'}", flush=True)
    if not ok:
        _fail(msg)


class CompileClock:
    """Sums the backend compile time JAX reports inside a ``with`` block."""

    def __enter__(self):
        import jax
        from jax._src import dispatch

        self.seconds = 0.0
        self.count = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == self._event:
            self.seconds += duration
            self.count += 1


def _peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _cut(arch: str, n_layers: int):
    from repro.configs import get_arch

    return dataclasses.replace(get_arch(arch), n_layers=n_layers)


def _widths(cfg) -> str:
    return (
        f"{cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} head_dim={cfg.hd} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} qkv_bias={cfg.qkv_bias} "
        f"tied={cfg.tie_embeddings} rope_theta={cfg.rope_theta:g}"
    )


def even_token_share(prompt, response) -> float:
    """Reward: share of even token ids in the response. With random weights
    the arithmetic reward is always 0, so advantages (and the update) would
    vanish; this one varies inside every group."""
    return sum(1 for t in response if t % 2 == 0) / max(len(response), 1)


def reference_logprobs(cfg, params, trajs):
    """Float32 XLA forward of each trajectory's prompt + response: the
    logprob of every response token, one list per trajectory."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M
    from repro.rl.losses import token_logprobs

    seqs = [list(t.prompt) + list(t.response) for t in trajs]
    tokens = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, : len(s)] = s
    fwd = jax.jit(
        lambda p, x: token_logprobs(
            M.forward(cfg, p, x, impl="ref")[0][:, :-1], x[:, 1:]
        )
    )
    with jax.default_matmul_precision("highest"):
        lp = np.asarray(fwd(params, jnp.asarray(tokens)))
    # response token j sits at position len(prompt) + j and is predicted
    # by the logits one position earlier
    return [
        lp[i, len(t.prompt) - 1 : len(t.prompt) - 1 + len(t.response)]
        for i, t in enumerate(trajs)
    ]


def run_loop(cfg, *, seed: int, **size) -> None:
    """The StaleFlow loop through ``AsyncRLRuntime``; exits on a failed
    check."""
    import jax
    import numpy as np

    from repro.core.lifecycle import LifecycleEventKind
    from repro.kernels import ops
    from repro.runtime.async_runtime import AsyncRLRuntime, RuntimeConfig
    from repro.training.train_step import TRAINER_IMPL

    rcfg = RuntimeConfig(
        eta=size["eta"], batch_size=size["batch_size"],
        group_size=size["group_size"], n_instances=size["n_instances"],
        max_slots=size["max_slots"], max_len=size["max_len"],
        max_new_tokens=size["max_new_tokens"],
        total_steps=size["total_steps"], lr=size["lr"], seed=seed,
        paged_kv=True, share_prefix=True, kv_block_size=size["kv_block_size"],
        scheduler="tick", reward_fn=even_token_share,
    )
    print(f"config: {_widths(cfg)}", flush=True)
    print(
        f"config: batch={rcfg.batch_size}x{rcfg.group_size} "
        f"max_new_tokens={rcfg.max_new_tokens} max_len={rcfg.max_len} "
        f"instances={rcfg.n_instances} slots={rcfg.max_slots} eta={rcfg.eta} "
        f"steps={rcfg.total_steps} seed={seed}",
        flush=True,
    )
    with CompileClock() as clock:
        t0 = time.perf_counter()
        rt = AsyncRLRuntime(cfg, rcfg)
        jax.block_until_ready(rt.params)
        setup_s = time.perf_counter() - t0
        inst = rt.instances[0]
        print(
            f"impl: prefill={ops.resolve_impl(inst.prefill_runner.impl)} "
            f"paged_decode={ops.resolve_impl(inst.paged_decode_runner.impl)} "
            f"trainer={TRAINER_IMPL}",
            flush=True,
        )
        # the reference check needs the version-0 weights after the loop has
        # replaced them; a host copy keeps device memory as the runtime has it
        params_v0 = jax.device_get(rt.params)
        ref_trajs = []

        def on_completed(e):
            t = e.traj
            if len(ref_trajs) < REF_TRAJS and all(v == 0 for v, _ in t.segments):
                ref_trajs.append(t)

        rt.lifecycle.subscribe(LifecycleEventKind.COMPLETED, on_completed)

        step_s = []
        last = [time.perf_counter(), clock.seconds]

        def progress(rec):
            jax.block_until_ready(rt.params)
            now = time.perf_counter()
            step_s.append(now - last[0])
            compile_s = clock.seconds - last[1]
            last[:] = [now, clock.seconds]
            print(
                f"step {rec.step}: wall_s={step_s[-1]:.3f} "
                f"compile_s={compile_s:.3f} loss={rec.loss:+.6f} "
                f"is_ratio={rec.mean_is_ratio:.6f} reward={rec.mean_reward:.4f} "
                f"staleness={rec.staleness_hist}",
                flush=True,
            )

        rt.run(max_ticks=2000, progress=progress)
        wall = time.perf_counter() - t0
    handles = list(rt.instances.values())
    commands = dict(rt.coordinator.stats.commands)
    print(
        f"rollout: decode_tokens={sum(h.decode_tokens for h in handles)} "
        f"decode_steps={sum(h.decode_steps for h in handles)} "
        f"prefill_tokens={sum(h.prefill_tokens for h in handles)} "
        f"shared_prefix_hits={sum(h.shared_prefix_hits for h in handles)} "
        f"block_copies={sum(h.block_copies for h in handles)} "
        f"preemptions={sum(h.preemptions for h in handles)}",
        flush=True,
    )
    print(f"coordinator: commands={commands}", flush=True)
    print(
        f"time: compile_s={clock.seconds:.3f} compiles={clock.count} "
        f"setup_s={setup_s:.3f} wall_s={wall:.3f}",
        flush=True,
    )
    print(f"memory: peak_bytes_in_use={_peak_bytes(jax.devices()[0])}",
          flush=True)

    hist = rt.history
    _check(len(hist) == rcfg.total_steps,
           f"{len(hist)} of {rcfg.total_steps} steps landed")
    _check(
        all(math.isfinite(r.loss) and math.isfinite(r.mean_is_ratio)
            for r in hist),
        "every loss and IS ratio is finite",
    )
    stale = rt.manager.max_consumed_staleness()
    _check(stale <= rcfg.eta, f"max consumed staleness {stale} <= eta")
    _check(commands.get("Pull", 0) >= 1,
           f"{commands.get('Pull', 0)} Pull(s) after a weight update")
    _check(len(ref_trajs) > 0,
           f"{len(ref_trajs)} version-0 trajectories for the reference")
    ref = reference_logprobs(cfg, params_v0, ref_trajs)
    diff = np.concatenate([
        np.abs(np.asarray(t.behavior_logprobs) - r)
        for t, r in zip(ref_trajs, ref)
    ])
    print(
        f"reference: trajectories={len(ref_trajs)} tokens={diff.size} "
        f"mean_abs_diff={diff.mean():.6f} max_abs_diff={diff.max():.6f}",
        flush=True,
    )
    _check(
        diff.mean() <= REF_MEAN_ATOL and diff.max() <= REF_MAX_ATOL,
        f"behavior logprobs within {REF_MEAN_ATOL} (mean) and "
        f"{REF_MAX_ATOL} (max) of the float32 forward",
    )


def _sharded_trajs(vocab: int, seed: int, max_new: int):
    """Seeded prompts mixing every admission path: two 3-member groups
    (one shared prefill, copy-on-write tails), a straggler that forks a
    resident prefix a wave later, and three singles."""
    import numpy as np

    from repro.core.types import Trajectory

    rng = np.random.RandomState(seed)
    prompts = [list(rng.randint(3, vocab, 21 + 8 * g)) for g in range(2)]
    first = [
        Trajectory(traj_id=3 * g + m, prompt=list(prompts[g]), group_id=g,
                   max_new_tokens=max_new)
        for g in range(2) for m in range(3)
    ]
    later = [Trajectory(traj_id=6, prompt=list(prompts[0]), group_id=0,
                        max_new_tokens=max_new)]
    later += [
        Trajectory(traj_id=7 + i, prompt=list(rng.randint(3, vocab, 9 + i)),
                   max_new_tokens=max_new)
        for i in range(3)
    ]
    return first, later


def _drive(inst, first, later):
    inst.route_many(first)
    done = {}
    for step in range(400):
        if step == 3:
            inst.route_many(later)
        for t in inst.step():
            done[t.traj_id] = (list(t.response), list(t.behavior_logprobs))
        if len(done) == len(first) + len(later):
            break
    return done


def run_sharded(cfg, *, seed: int, shards: int, **size) -> None:
    """One ``ShardedBackend`` over ``shards`` devices against the
    single-device paged engine; exits on a failed check."""
    import jax
    import numpy as np

    from repro.kernels import ops
    from repro.models import model as M
    from repro.rollout.backend import create_backend

    # the sharded runners force the jnp reference kernels; the reference
    # engine uses them too, so the comparison isolates the sharding
    ops.set_default_impl("ref")
    print(f"config: {_widths(cfg)} shards={shards}", flush=True)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    kw = dict(
        cfg=cfg, params=params, version=0, max_slots=size["max_slots"],
        max_len=size["max_len"], temperature=0.0, eos_id=-1, seed=seed,
        paged=True, kv_block_size=size["kv_block_size"],
        kv_pool_blocks=size["kv_pool_blocks"], share_prefix=True,
    )
    results, engines = [], []
    with CompileClock() as clock:
        for kind in ("jax", "sharded"):
            extra = {"shard_count": shards} if kind == "sharded" else {}
            inst = create_backend(kind, 0, **kw, **extra)
            first, later = _sharded_trajs(cfg.vocab_size, seed,
                                          size["max_new_tokens"])
            t0 = time.perf_counter()
            results.append(_drive(inst, first, later))
            engines.append(inst)
            print(
                f"engine {kind}: wall_s={time.perf_counter() - t0:.3f} "
                f"decode_tokens={inst.decode_tokens} "
                f"shared_prefix_hits={inst.shared_prefix_hits} "
                f"preemptions={inst.preemptions}",
                flush=True,
            )
    ref, shd = results
    print(f"sharded: k_pool_shards={engines[1].shard_sizes()}", flush=True)
    print(f"time: compile_s={clock.seconds:.3f} compiles={clock.count}",
          flush=True)
    print(
        "memory: peak_bytes_in_use="
        f"{[_peak_bytes(d) for d in jax.devices()[:shards]]}",
        flush=True,
    )
    _check(len(ref) == len(shd) == 10 and set(ref) == set(shd),
           f"{len(ref)}/{len(shd)} of 10 trajectories finished on both")
    # With random weights the top two logits can tie to rounding, and greedy
    # decoding then picks either: compare each trajectory up to and
    # including its first differing token (where a tie shows as equal
    # logprobs of the two picks) and not past it, where contexts differ.
    diffs, forked = [], 0
    for t in sorted(ref):
        toks_r, lp_r = ref[t]
        toks_s, lp_s = shd[t]
        d = next((i for i, (a, b) in enumerate(zip(toks_r, toks_s)) if a != b),
                 len(toks_r) - 1)
        forked += toks_r != toks_s
        diffs.append(np.abs(np.asarray(lp_s[: d + 1]) - np.asarray(lp_r[: d + 1])))
    diff = np.concatenate(diffs)
    total = sum(len(r[0]) for r in ref.values())
    print(
        f"sharded: tokens_compared={diff.size} of {total} "
        f"trajectories_forked={forked} max_abs_logprob_diff={diff.max():.3e}",
        flush=True,
    )
    _check(forked <= 1, f"{forked} of {len(ref)} greedy streams forked (<= 1)")
    _check(diff.max() <= SHARDED_ATOL,
           f"sharded logprobs within {SHARDED_ATOL} of the single-device")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded rollout path on 4 chips and "
                         "its single-device comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"no repro package under {ROOT / 'src'}: run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU found (JAX sees {devices[0].platform}); this script "
              "measures the chip and never falls back to the CPU")
    need = SHARDED["shards"] if args.four_chips else 1
    if len(devices) < need:
        _fail(f"needs {need} TPU chips, JAX sees {len(devices)}")
    from repro.launch.compile_cache import use_compile_cache

    print(f"device: {devices[0].device_kind} count={len(devices)} "
          f"compile_cache={use_compile_cache()}", flush=True)
    if args.four_chips:
        size = dict(SHARDED)
        cfg = _cut(size.pop("arch"), size.pop("n_layers"))
        run_sharded(cfg, seed=args.seed, **size)
    else:
        size = dict(LOOP)
        cfg = _cut(size.pop("arch"), size.pop("n_layers"))
        run_loop(cfg, seed=args.seed, **size)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))


if __name__ == "__main__":
    main()
