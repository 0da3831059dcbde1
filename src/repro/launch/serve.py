"""Serving launcher: continuous-batching generation on one model replica.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
        --requests 8 --slots 4 --max-new 12

Reduced configs execute numerically on CPU; the full-size serve_step for
every (arch x decode shape) cell is exercised by the dry-run.

``--trace PATH`` exports a Perfetto-loadable Chrome trace of the run
(queue/decode segments per request, reward-worker activity); ``--log-json``
switches the structured log to NDJSON.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax

from repro.configs import get_arch
from repro.core.types import Trajectory, next_traj_id
from repro.data.tasks import ArithmeticDataset
from repro.data.tokenizer import decode as tok_decode
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as M
from repro.obs import get_logger, setup_logging
from repro.rollout.backend import create_backend


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument(
        "--no-compact-decode", action="store_true",
        help="decode all slots every step (seed behavior) instead of "
             "compacting to the active power-of-two bucket",
    )
    ap.add_argument(
        "--paged", action="store_true",
        help="block-paged KV cache (shared pool + per-trajectory block "
             "tables) instead of dense per-slot rows",
    )
    ap.add_argument(
        "--block-size", type=int, default=16,
        help="tokens per KV block with --paged",
    )
    ap.add_argument(
        "--group-size", type=int, default=1,
        help="sample this many responses per prompt (GRPO-style group "
             "rollout); with --paged the shared prompt prefills once and "
             "its full KV blocks are refcount-shared across the group",
    )
    ap.add_argument(
        "--no-share-prefix", action="store_true",
        help="disable prefix sharing for group rollout (ablation)",
    )
    ap.add_argument(
        "--shards", type=int, default=1,
        help="devices this replica spans (requires --paged): params and "
             "the paged KV pool are head-sharded over a ('tensor',) mesh; "
             "on CPU set XLA_FLAGS=--xla_force_host_platform_device_count"
             "=<n> first",
    )
    ap.add_argument(
        "--kv-heads", type=int, default=0,
        help="override the reduced config's n_kv_heads (most reduced "
             "configs keep the GQA ratio with 1 KV head, which cannot "
             "split; --shards needs n_kv_heads %% shards == 0)",
    )
    ap.add_argument(
        "--score", action="store_true",
        help="verify completions through a threaded RewardServer (worker "
             "pool on the trajectory-lifecycle bus) overlapping decode — "
             "the disaggregated reward phase, standalone",
    )
    ap.add_argument(
        "--reward-workers", type=int, default=2,
        help="reward worker threads with --score",
    )
    ap.add_argument(
        "--score-url", default=None, metavar="URL",
        help="route completions through a RewardHub whose default route is "
             "a remote submit-then-poll judge at URL (HttpVerifier: "
             "per-request timeout, capped-backoff retries, circuit "
             "breaker); implies --score. The in-process RewardModel keeps "
             "the 'math' tag",
    )
    ap.add_argument(
        "--score-sandbox", default=None, metavar="SPEC",
        help="register a subprocess-sandboxed code-execution verifier "
             "under the 'code' task tag (resource/time-limited, "
             "kill-on-timeout); SPEC is inline Python source defining "
             "score(prompt_ids, response_ids), or @path/to/program.py; "
             "implies --score",
    )
    ap.add_argument(
        "--score-timeout", type=float, default=5.0,
        help="per-request / sandbox wall deadline (s) for hub verifiers",
    )
    ap.add_argument(
        "--score-retries", type=int, default=3,
        help="bounded attempts per remote-judge protocol step",
    )
    ap.add_argument(
        "--trace", default=None, metavar="PATH",
        help="export a Chrome trace (Perfetto-loadable) of the run",
    )
    ap.add_argument(
        "--log-json", action="store_true",
        help="structured NDJSON logs instead of human-readable lines",
    )
    ap.add_argument(
        "--wall-limit", type=float, default=120.0,
        help="seconds of decoding before giving up; the run exits 1 if "
             "any request is still unserved then (compilation counts)",
    )
    args = ap.parse_args()
    if args.score_url or args.score_sandbox:
        args.score = True
    setup_logging(json_mode=args.log_json)
    log = get_logger("serve")
    use_compile_cache()

    cfg = get_arch(args.arch).reduced()
    if args.kv_heads:
        import dataclasses

        cfg = dataclasses.replace(cfg, n_kv_heads=args.kv_heads)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(
        cfg=cfg, params=params, version=0, max_slots=args.slots,
        max_len=64, temperature=args.temperature,
        compact_decode=not args.no_compact_decode,
        paged=args.paged, kv_block_size=args.block_size,
        share_prefix=not args.no_share_prefix,
    )
    if args.shards > 1:
        if not args.paged:
            raise SystemExit("--shards requires --paged (sharded KV pool)")
        inst = create_backend("sharded", 0, shard_count=args.shards, **kw)
        log.info(
            "sharded replica",
            extra={"shards": args.shards, "visible": jax.device_count()},
        )
    else:
        inst = create_backend("jax", 0, **kw)
    ds = ArithmeticDataset(args.requests, seed=2)
    n_requests = args.requests * args.group_size

    tracer = None
    lifecycle = None
    if args.trace or args.score:
        from repro.core import TrajectoryLifecycle

        lifecycle = TrajectoryLifecycle()
    if args.trace:
        from repro.obs import TrajectoryTracer

        tracer = TrajectoryTracer(lifecycle)
        inst.on_admit = tracer.on_admit
        inst.on_preempt = tracer.on_preempt

    reward_server = None
    hub = None
    if args.score:
        from repro.core import RewardServer, RewardServerConfig
        from repro.reward.verifier import RewardModel

        verifier = RewardModel(lambda prompt: ds.answer_for(prompt))
        if args.score_url or args.score_sandbox:
            from repro.reward import (
                DEFAULT_ROUTE,
                CircuitBreaker,
                HttpVerifier,
                RetryPolicy,
                RewardHub,
                SandboxVerifier,
            )

            hub = RewardHub(default=verifier, tracer=tracer)
            hub.register("math", verifier)
            if args.score_sandbox:
                hub.register("code", SandboxVerifier.from_spec(
                    args.score_sandbox, timeout_s=args.score_timeout,
                ))
            if args.score_url:
                remote = HttpVerifier(
                    args.score_url,
                    policy=RetryPolicy(
                        max_attempts=max(1, args.score_retries),
                        request_timeout_s=args.score_timeout,
                    ),
                    breaker=CircuitBreaker(),
                    total_timeout_s=args.score_timeout * 4,
                )
                hub.register("remote", remote)
                hub.register(DEFAULT_ROUTE, remote)
            verifier = hub
            log.info("reward hub routes", extra={"tags": hub.tags()})
        reward_server = RewardServer(
            verifier,
            lifecycle,
            RewardServerConfig(n_workers=args.reward_workers),
            tracer=tracer,
        )
        reward_server.start()  # worker pool: scoring overlaps decode

    for gid, p in enumerate(ds.problems):
        wave = [
            Trajectory(
                traj_id=next_traj_id(), prompt=list(p.prompt_ids),
                group_id=gid if args.group_size > 1 else -1,
                max_new_tokens=args.max_new,
            )
            for _ in range(args.group_size)
        ]
        if lifecycle is not None:
            # span opens at route — before route_many, which may admit
            # synchronously (the same ordering execute_commands uses)
            for t in wave:
                lifecycle.routed(t, inst.inst_id, 0)
        inst.route_many(wave)

    t0 = time.time()
    done = []
    while len(done) < n_requests and time.time() - t0 < args.wall_limit:
        s0 = time.perf_counter()
        finished = inst.step()
        if tracer is not None:
            tracer.activity("decode", s0, time.perf_counter(), track="serve")
        for t in finished:
            done.append(t)
            log.info(
                "completion",
                extra={
                    "prompt": tok_decode(t.prompt),
                    "response": tok_decode(t.response),
                },
            )
            if lifecycle is not None:
                lifecycle.completed(t, inst.inst_id)
    dt = time.time() - t0
    log.info(
        "served",
        extra={
            "requests": len(done),
            "decode_tokens": inst.decode_tokens,
            "wall_s": round(dt, 2),
            "tok_per_s": round(inst.decode_tokens / dt, 1),
            "tok_per_step": round(
                inst.decode_tokens / max(inst.decode_steps, 1), 2
            ),
        },
    )
    if args.group_size > 1 and args.paged:
        log.info(
            "prefix sharing",
            extra={
                "shared_admits": inst.shared_prefix_hits,
                "prefill_tokens_saved": inst.prefill_tokens_saved,
            },
        )
    if reward_server is not None:
        reward_server.drain()
        reward_server.stop()
        correct = sum(1 for t in done if t.reward == 1.0)
        pct = reward_server.latency_percentiles((0.5, 0.95))
        log.info(
            "reward server",
            extra={
                "scored": reward_server.scored,
                "correct": correct,
                "queue_p50_ms": round(1e3 * (pct[0.5] or 0), 2),
                "queue_p95_ms": round(1e3 * (pct[0.95] or 0), 2),
            },
        )
        if hub is not None:
            log.info("reward hub", extra={"stats": hub.stats()})
    if tracer is not None:
        from repro.obs import export_chrome_trace

        trace = export_chrome_trace(tracer, args.trace)
        log.info(
            "trace written",
            extra={
                "path": args.trace,
                "events": len(trace["traceEvents"]),
                "spans": trace["otherData"]["spans"],
            },
        )
    if len(done) < n_requests:
        log.error(
            "wall limit reached with requests unserved",
            extra={
                "served": len(done),
                "requests": n_requests,
                "wall_limit_s": args.wall_limit,
            },
        )
        sys.exit(1)


if __name__ == "__main__":
    main()
