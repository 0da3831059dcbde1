"""Distributed training launcher.

Builds the production mesh, shards params/optimizer/batch with the
repository sharding rules, and drives the RL train step. On this CPU
container it runs REDUCED configs on a degenerate mesh (numerically); full
configs are exercised by the dry-run (``repro.launch.dryrun``). On a real
TPU slice the same file is the per-host entry point (jax.distributed
initialization + the identical mesh/sharding code paths).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
        --steps 3 --batch 8 --seq 64 [--compress-dp] [--ckpt-dir DIR]

``--trace PATH`` exports a Chrome trace of the step loop (train-step
spans, background PS-push activity); ``--log-json`` switches the
structured log to NDJSON.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.distributed import sharding as shd
from repro.distributed.collectives import make_dp_allreduce
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.obs import get_logger, setup_logging
from repro.training import checkpoint as ckpt_lib
from repro.training.optimizer import AdamWConfig, init_opt_state
from repro.training.train_step import TRAINER_IMPL, make_rl_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--compress-dp", action="store_true",
                    help="int8 gradient all-reduce demo (shard_map)")
    ap.add_argument("--ps-push", action="store_true",
                    help="publish each step's params to a ParameterServer "
                         "through the BackgroundPusher: Push overlaps the "
                         "next training step (Appendix A)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (only sensible on real HW)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export a Chrome trace (Perfetto-loadable) of the "
                         "step loop")
    ap.add_argument("--log-json", action="store_true",
                    help="structured NDJSON logs instead of human-readable "
                         "lines")
    args = ap.parse_args()
    setup_logging(json_mode=args.log_json)
    log = get_logger("train")
    use_compile_cache()

    tracer = None
    if args.trace:
        from repro.obs import TrajectoryTracer

        tracer = TrajectoryTracer()  # activity tracks only: no lifecycle

    cfg = get_arch(args.arch)
    if not args.full_size:
        cfg = cfg.reduced()
    mesh = make_host_mesh()
    log.info(
        "mesh built",
        extra={
            "mesh": dict(mesh.shape),
            "arch": cfg.name,
            "params_m": round(cfg.n_params / 1e6, 1),
        },
    )

    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    opt = init_opt_state(params)
    p_sh = shd.params_shardings(mesh, params)
    o_sh = shd.opt_shardings(mesh, opt)
    params = jax.device_put(params, p_sh)
    opt = jax.device_put(opt, o_sh)

    b, t = args.batch, args.seq
    batch = {
        "tokens": jax.random.randint(key, (b, t), 3, cfg.vocab_size),
        "behavior_logprobs": jnp.full((b, t), -2.0),
        "mask": jnp.ones((b, t)),
        "advantages": jnp.linspace(-1.0, 1.0, b),
    }
    b_sh = shd.train_batch_shardings(mesh, batch)
    batch = jax.device_put(batch, b_sh)

    step = jax.jit(
        make_rl_train_step(
            cfg, AdamWConfig(lr=args.lr), remat=args.remat,
            accum_steps=args.accum, impl=TRAINER_IMPL,
        ),
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, None),
    )
    if args.compress_dp:
        # demonstration: grads would flow through the compressed DP
        # all-reduce on a multi-host mesh; on 1 device it's an identity
        make_dp_allreduce(mesh, compress=True)
        log.info("compressed DP all-reduce enabled (int8, global-scale psum)")

    pusher = None
    if args.ps_push:
        from repro.core import BackgroundPusher, ParameterServer

        ps = ParameterServer()
        ps.push(params, 0)
        pusher = BackgroundPusher(ps, tracer=tracer).start()
        log.info("background PS push enabled (overlaps the next step)")

    for i in range(args.steps):
        t0 = time.time()
        s0 = time.perf_counter()
        params, opt, metrics = step(params, opt, batch)
        loss = float(metrics["loss"])
        if tracer is not None:
            tracer.activity(
                "train_step", s0, time.perf_counter(),
                track="trainer", args={"step": i},
            )
        if pusher is not None:
            pusher.push(params, i + 1)  # returns immediately
        log.info(
            "step",
            extra={
                "step": i,
                "loss": round(loss, 4),
                "grad_norm": round(float(metrics["grad_norm"]), 3),
                "wall_s": round(time.time() - t0, 2),
            },
        )

    if pusher is not None:
        pusher.flush()
        log.info(
            "PS published",
            extra={
                "version": pusher.ps.version,
                "background_pushes": pusher.pushes,
            },
        )
        pusher.stop()

    if args.ckpt_dir:
        path = ckpt_lib.save_checkpoint(args.ckpt_dir, args.steps, params, opt)
        log.info("checkpoint written", extra={"path": path})

    if tracer is not None:
        from repro.obs import export_chrome_trace

        trace = export_chrome_trace(tracer, args.trace)
        log.info(
            "trace written",
            extra={
                "path": args.trace,
                "events": len(trace["traceEvents"]),
            },
        )


if __name__ == "__main__":
    main()
