"""Read the numbers the limits are set from, on the chip, in one process.

    python3 chipbench/calibrate.py --workload qwen2-1.5b.rlvr \\
        --seeds 11 12 13 --fault-seeds 21 22 23 --out FILE

Each seed of ``--seeds`` drives a run of the cell through its first
training steps with the control in the program's place (``run_cell``'s
``control`` mode, checked against the committed limits, so its ``correct``
has to read false), and records every number the check can compare three
ways: the program's against the reference, the control's (the reference
in bfloat16 throughout) against the reference, and the program's against
a reference whose attention products run at ``highest``. The compared
numbers come from those steps and the trajectories they consumed, which
warming the shapes touches not at all (``chipbench/tests/test_check.py``
shows it), so calibration skips that warm-up and the window. Every
token's logprob, each step's loss and ratio and each leaf's norms go to
``FILE.raw.json``.

Each seed of ``--fault-seeds`` runs once per planted fault (``half_batch``:
half the rows of each batch left out of the mean; ``token``: every
sampled token recorded one id off, where the engine produces it).
``frozen`` (a step that hands back its weights) reads 1 on ``update_gap``
by construction and needs no run. The benchmark's own runs never run
this; ``PERF.md`` gives the readings and the limits set from them.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
FAULTS = ("half_batch", "token")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from chipbench import harness

    harness.enable_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU found", file=sys.stderr)
        sys.exit(2)
    from chipbench import check, traffic

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    _, conf = harness.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    runs = [(s, None) for s in args.seeds]
    runs += [(s, f) for f in FAULTS for s in args.fault_seeds]
    rows, raw = [], []
    for seed, fault in runs:
        t0 = time.perf_counter()
        keep = {}
        res = harness.run_cell(
            cell, bench, seed=seed, seconds=0.0, trace=False, t_start=t0,
            fault=fault, control=fault is None, warm=False,
            keep=keep,
        )
        row = {"seed": seed, "fault": fault, "correct": res.correct,
               "seconds": time.perf_counter() - t0,
               "peak_bytes": res.device["memory_peak_bytes"]}
        if "reference" in keep:
            ref, ids = keep["reference"], [t["id"] for t in keep["trajs"]]
            grads = ref.grad_norms
            row["program"] = check.numbers(keep["program"], ref, grads, ids)
            if fault is None:
                row["control"] = check.numbers(keep["control"], ref, grads, ids)
                hi = check.reference_readings(
                    conf, seed, mix, keep["capture"], keep["trajs"],
                    precision=conf["matmul_precision"],
                    attn_precision="highest")
                row["program_vs_attn_highest"] = check.numbers(
                    keep["program"], hi, hi.grad_norms, ids)
                row["control_vs_attn_highest"] = check.numbers(
                    keep["control"], hi, hi.grad_norms, ids)
                keep["attn_highest"] = hi
            raw.append(_raw(seed, fault, keep, check))
        rows.append(row)
        print(json.dumps(row), flush=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
        Path(args.out + ".raw.json").write_text(json.dumps(raw))
        del res, keep
        gc.collect()


def _raw(seed, fault, keep, check):
    """Every token's logprob under each reading, each step's loss and
    ratio, each leaf's norms."""
    names = [k for k in ("program", "reference", "control", "attn_highest")
             if k in keep]
    tokens = []
    for t in keep["trajs"]:
        versions = check.token_versions(t)
        for j, v in enumerate(versions):
            tokens.append([t["id"], j, v] + [
                float(keep[k].logprobs[t["id"]][j]) for k in names])
    return {
        "seed": seed, "fault": fault, "columns": ["id", "j", "version"] + names,
        "tokens": tokens,
        "losses": {k: keep[k].losses for k in names},
        "ratios": {k: keep[k].ratios for k in names},
        "grad_norms": {k: keep[k].grad_norms for k in names},
        "change_norms": {k: keep[k].change_norms for k in names},
    }


if __name__ == "__main__":
    main()
