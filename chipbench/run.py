"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload qwen2-1.5b.rlvr --seed 7 \\
        --seconds 45 --trace 0

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a model
configuration (``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<traffic>.json``). The run builds the job from the
seed, warms up every shape the cell uses, measures for ``--seconds``
seconds (``--trace 1``: a few traced RL steps instead, for the per-layer
metrics), compares what the job produced with the plain reference, and
prints one JSON object as its last line of standard output. The compared
numbers and their limits are the last lines of standard error.

It needs a TPU: without one, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _fail(msg: str, code: int = 2) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def _log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        _fail(f"--seed must be >= 0, got {args.seed}")

    bench_path = ROOT / "BENCHMARK.json"
    if not bench_path.is_file():
        _fail(f"no {bench_path}")
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        _fail(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
    cell = cells[args.workload]
    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"no program under {ROOT / 'src'}: run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    from chipbench import harness

    # the cache directory is set before JAX is first used
    harness.enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU found (JAX sees {devices[0].platform}); the benchmark "
              "measures the chip and never falls back to the CPU")
    if len(devices) < cell["chips"]:
        _fail(f"{args.workload} needs {cell['chips']} chips, JAX sees "
              f"{len(devices)}")
    from chipbench import work

    work.peak(devices[0].device_kind)  # an unknown chip is an error
    result = harness.run_cell(
        cell, bench, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, log=_log,
    )
    emit(result)


def emit(result) -> None:
    """The compared numbers beside their limits, last on standard error,
    and the result line, last on standard output."""
    for c in result.checks:
        _log(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
             f"{'ok' if c['ok'] else 'FAILED'}")
    line = {
        "correct": result.correct,
        "attempted": result.attempted,
        "failed": result.failed,
        "metrics": result.metrics,
        "device": result.device,
    }
    if result.breakdown is not None:
        line["breakdown"] = result.breakdown
    line["checks"] = {c["name"]: {"value": _finite(c["value"]),
                                  "limit": c["limit"]}
                      for c in result.checks}
    print(json.dumps(line), flush=True)


def _finite(x):
    """JSON has no infinity: a number that could not be read is null."""
    return x if x is None or math.isfinite(x) else None


if __name__ == "__main__":
    main()
