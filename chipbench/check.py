"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, the plain
reference (``chipbench.reference``, with the model equations the
configuration's ``"reference"`` key names in ``chipbench/refmodels``)
follows the job's first training steps from the seed and scores every
trajectory those steps consumed.
Each number below is compared with its limit in
``chipbench/limits/<workload>.json``, which names the numbers compared;
``PERF.md`` gives the readings each limit was set from.

* ``lp_gap``: the widest gap between a consumed trajectory's behaviour
  logprob, as the engine recorded it, and the reference's logprob of that
  token under the version that generated it. The tokens are sampled at
  temperature 1, so a gap to the reference's best token says nothing; the
  gap of the token's own logprob does. It covers full prefill, suffix
  prefill of shared-prefix forks, paged decode over copy-on-write blocks
  and re-prefill after a Pull. ``lp_mean_gap`` is the mean gap over the
  same tokens.
* ``loss_gap`` and ``ratio_gap``: the widest gap, over the checked steps,
  between the trainer's DAPO loss (and mean importance ratio) and the
  reference's on the same consumed batch.
* ``grad_gap``: the first step's clipped gradient, worked out from AdamW's
  first moment after that step, by the worst leaf: the gap between the
  program's norm and the reference's, over the larger of the reference's
  norm of that leaf and of the median leaf.
* ``update_gap``: the same for the change of the weights over the checked
  steps.
* ``staleness``: the largest realized staleness of a consumed batch,
  against eta.
* ``lifecycle``: trajectories that ended in other than one terminal event,
  or were rewarded twice.

Leaves whose reference gradient is under a thousandth of the median
leaf's (the key bias, whose gradient is nought under softmax) move under
Adam by round-off alone and are left out of both leaf comparisons.

The reference computes in the precision the configuration states: float32
at its ``matmul_precision``. The control is the reference one step lower,
in bfloat16 throughout, put in the program's place: with ``control`` the
compared numbers are the control's, and ``correct`` has to come out false.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench.reference import even_token_share  # noqa: F401  (the reward)

# a leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone
STILL_LEAF = 1e-3


def weights_seed(seed: int) -> int:
    """The seed the job's weights and sampling keys are drawn from: the
    run's seed folded into the 31 bits a PRNG key takes."""
    return seed % (1 << 31)


@dataclass
class Readings:
    losses: List[float]
    ratios: List[float]
    grad_norms: Dict[str, float]
    change_norms: Dict[str, float]
    logprobs: Dict[int, np.ndarray]


def consumed(capture) -> List[Dict[str, Any]]:
    """Every trajectory the checked steps consumed, by id. They are fixed
    once the last checked step has run, however long the run goes on."""
    seen = {t["id"]: t for batch in capture.batches for t in batch}
    return [seen[i] for i in sorted(seen)]


def token_versions(t: Dict[str, Any]) -> List[int]:
    """The version that generated each response token of ``t``."""
    return [v for v, n in t["segments"] for _ in range(n)]


def _leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
              moving: List[str]) -> float:
    med = float(np.median([ref[k] for k in ref]))
    return max(
        abs(prog[k] - ref[k]) / max(ref[k], med) for k in moving
    ) if moving else float("inf")


def numbers(prog: Readings, ref: Readings, ref_grads: Dict[str, float],
            ids: List[int]) -> Dict[str, float]:
    """The compared numbers of ``prog`` against ``ref``. ``ref_grads`` (the
    reference's first gradient) decides which leaves move."""
    med = float(np.median(list(ref_grads.values())))
    moving = [k for k, g in ref_grads.items() if g >= STILL_LEAF * med]
    gaps = np.concatenate([np.abs(
        np.asarray(prog.logprobs[i], np.float64) -
        np.asarray(ref.logprobs[i], np.float64)) for i in ids] or [[]])
    k = min(len(prog.losses), len(ref.losses))
    inf = float("inf")
    return {
        "lp_gap": float(gaps.max()) if gaps.size else inf,
        "lp_mean_gap": float(gaps.mean()) if gaps.size else inf,
        "loss_gap": max(abs(a - b) for a, b in
                        zip(prog.losses[:k], ref.losses[:k])) if k else inf,
        "ratio_gap": max(abs(a - b) for a, b in
                         zip(prog.ratios[:k], ref.ratios[:k])) if k else inf,
        "grad_gap": _leaf_gap(prog.grad_norms, ref.grad_norms, moving),
        "update_gap": _leaf_gap(prog.change_norms, ref.change_norms, moving),
    }


def program_readings(capture, trajs: List[Dict[str, Any]]) -> Readings:
    return Readings(
        losses=list(capture.losses), ratios=list(capture.ratios),
        grad_norms=dict(capture.grad_norms),
        change_norms=dict(capture.change_norms),
        logprobs={t["id"]: np.asarray(t["behavior_logprobs"], np.float32)
                  for t in trajs},
    )


def reference_readings(conf, seed, mix, capture, trajs, **kw) -> Readings:
    from chipbench import reference

    r = reference.follow(conf, weights_seed(seed), mix.lr, capture.batches,
                         trajs, width=mix.max_len,
                         batch_rows=mix.batch_size * mix.group_size, **kw)
    return Readings(r.losses, r.ratios, r.grad_norms, r.change_norms,
                    r.logprobs)


def compare(conf: Dict[str, Any], seed: int, mix, capture,
            limits: Dict[str, float], *, staleness: int, eta: int,
            violations: int, control: bool = False,
            keep: Optional[Dict[str, Any]] = None) -> List[Dict[str, Any]]:
    """Every compared number beside its limit: those ``limits`` names, and
    the guarantees. With ``control``, the reference in bfloat16 stands in
    the program's place. ``keep``, where given, receives the readings."""
    import jax.numpy as jnp

    trajs = consumed(capture)
    ids = [t["id"] for t in trajs]
    if not len(capture.losses) >= len(capture.batches) > 0:
        nums = {k: float("inf") for k in limits}
    else:
        precision = conf["matmul_precision"]
        ref = reference_readings(conf, seed, mix, capture, trajs,
                                 precision=precision)
        prog = program_readings(capture, trajs)
        if keep is not None:
            keep.update(capture=capture, trajs=trajs, reference=ref,
                        program=prog)
        if control:
            prog = reference_readings(conf, seed, mix, capture, trajs,
                                      dtype=jnp.bfloat16, precision=precision)
            if keep is not None:
                keep["control"] = prog
        nums = numbers(prog, ref, ref.grad_norms, ids)
    nums = {k: nums[k] for k in limits}
    nums["staleness"] = float(staleness)
    nums["lifecycle"] = float(violations)
    limits = dict(limits, staleness=float(eta), lifecycle=0.0)
    return [
        {"name": k, "value": v, "limit": limits[k], "ok": bool(v <= limits[k])}
        for k, v in nums.items()
    ]
