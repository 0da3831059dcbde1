"""The plain reference's RL side: the DAPO loss and AdamW, and a run that
follows the job's first training steps, in straightforward ``jax.numpy``.

The model equations are the configuration's own: ``follow`` loads the
module its ``"reference"`` key names (``chipbench/refmodels``). Nothing
here imports the program or takes what it made. The weights come from the
seed by the same draws the program's initializer makes, so at version 0
both hold the same numbers without one handing them to the other; after
that the reference follows the training steps itself, from the token
batches the job consumed.

``dtype`` and ``precision`` pick how it computes. The reference is
float32 at the matmul precision the configuration states; on a TPU,
``default`` rounds each matmul's inputs to bfloat16. The control is the
step below float32: the same passes in bfloat16 throughout, on float32
master weights. ``attn_precision`` sets the precision of the two attention
products alone.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import refmodels

Params = Dict[str, Any]
# (params, (B, S) tokens) -> (B, S, padded vocab) float32 logits
Forward = Callable[[Params, Any], Any]


def seed_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(seed)


def token_logprobs(fwd: Forward, params: Params, tokens):
    """log p(tokens[:, t+1] | tokens[:, :t+1]) for t < S-1: (B, S-1)."""
    lg = fwd(params, tokens)[:, :-1]
    tgt = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return tgt - jax.scipy.special.logsumexp(lg, axis=-1)


# ------------------------------------------------------------------- training
@dataclass(frozen=True)
class Optim:
    lr: float
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    eps_low: float = 0.2          # DAPO clip-higher range
    eps_high: float = 0.28


def dapo_loss(fwd: Forward, o: Optim, params, batch):
    """Token-mean clipped policy gradient over the response tokens, and the
    mean importance ratio against the behaviour logprobs."""
    lp = token_logprobs(fwd, params, batch["tokens"])
    blp = batch["behavior_logprobs"][:, 1:]
    m = batch["mask"][:, 1:]
    adv = batch["advantages"][:, None]
    ratio = jnp.exp(lp - blp)
    obj = jnp.minimum(ratio * adv,
                      jnp.clip(ratio, 1 - o.eps_low, 1 + o.eps_high) * adv)
    denom = jnp.maximum(m.sum(), 1.0)
    return -(obj * m).sum() / denom, (ratio * m).sum() / denom


def train_step(fwd: Forward, o: Optim, params, state, batch,
               dtype=jnp.float32):
    """One DAPO step with global-norm clipping and AdamW (no decay) on
    float32 weights; the forward and backward passes run in ``dtype``.
    Returns (params, state, loss, mean ratio, clipped gradient)."""
    (loss, ratio), g = jax.value_and_grad(
        lambda p: dapo_loss(fwd, o, _cast(p, dtype), batch), has_aux=True
    )(params)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                         for x in jax.tree_util.tree_leaves(g)))
    scale = jnp.minimum(1.0, o.grad_clip / (gnorm + 1e-9))
    g = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32) * scale, g)
    step = state["step"] + 1
    b1c = 1 - o.b1 ** step
    b2c = 1 - o.b2 ** step
    m = jax.tree_util.tree_map(lambda m, g: o.b1 * m + (1 - o.b1) * g,
                               state["m"], g)
    v = jax.tree_util.tree_map(lambda v, g: o.b2 * v + (1 - o.b2) * g * g,
                               state["v"], g)
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - o.lr * (m / b1c) / (jnp.sqrt(v / b2c) + o.eps),
        params, m, v,
    )
    return new, {"m": m, "v": v, "step": step}, loss, ratio, g


def group_advantages(rewards: Sequence[float], groups: Sequence[int],
                     eps: float = 1e-6) -> np.ndarray:
    r = np.asarray(rewards, np.float64)
    g = np.asarray(groups)
    adv = np.zeros_like(r)
    for gid in np.unique(g):
        sel = g == gid
        adv[sel] = (r[sel] - r[sel].mean()) / (r[sel].std() + eps)
    return adv.astype(np.float32)


def even_token_share(prompt, response) -> float:
    """The jobs' reward: the share of even token ids in the response. With
    random weights a task reward would be 0 everywhere, and so would every
    advantage; this one varies inside every group."""
    return sum(1 for t in response if t % 2 == 0) / max(len(response), 1)


def make_batch(trajs: Sequence[Dict[str, Any]], width: int = 0,
               rows: int = 0) -> Dict[str, np.ndarray]:
    """The trainer's batch from consumed trajectories (``prompt``,
    ``response``, ``behavior_logprobs``, ``group``): right-padded tokens,
    behaviour logprobs and a mask on response positions, and group
    advantages of the reward. Padded to at least ``width`` columns and
    ``rows`` rows; padding is masked out, and under the causal mask later
    positions change nothing before them, so one compiled shape serves
    every batch."""
    t_max = max([width] + [len(t["prompt"]) + len(t["response"])
                           for t in trajs])
    b = max(rows, len(trajs))
    tokens = np.zeros((b, t_max), np.int32)
    blp = np.zeros((b, t_max), np.float32)
    mask = np.zeros((b, t_max), np.float32)
    for i, t in enumerate(trajs):
        seq = list(t["prompt"]) + list(t["response"])
        tokens[i, : len(seq)] = seq
        n = len(t["prompt"])
        blp[i, n : len(seq)] = t["behavior_logprobs"]
        mask[i, n : len(seq)] = 1.0
    rewards = [even_token_share(t["prompt"], t["response"]) for t in trajs]
    adv = np.zeros((b,), np.float32)
    adv[: len(trajs)] = group_advantages(rewards, [t["group"] for t in trajs])
    return {"tokens": tokens, "behavior_logprobs": blp, "mask": mask,
            "advantages": adv}


# ------------------------------------------------------------------- a run
@dataclass
class Readings:
    """What the reference computed along the job's first training steps."""

    losses: List[float]
    ratios: List[float]
    grad_norms: Dict[str, float]        # per leaf, first step, clipped
    change_norms: Dict[str, float]      # per leaf, params after last - init
    logprobs: Dict[int, np.ndarray]     # traj id -> response token logprobs


def leaf_names(tree) -> List[str]:
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(x, np.float64).ravel())) for p, x in flat}


def _cast(tree, dtype):
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


def follow(conf: Dict[str, Any], seed: int, lr: float,
           batches: Sequence[Sequence[Dict[str, Any]]],
           rollouts: Sequence[Dict[str, Any]], *,
           dtype=jnp.float32, precision: str = "highest",
           attn_precision: Optional[str] = None,
           rows: int = 8, width: int = 0, batch_rows: int = 0) -> Readings:
    """Follow the job's first ``len(batches)`` training steps from the
    seed, and score every rollout token under the version that generated
    it.

    ``batches[k]`` holds the trajectories step ``k + 1`` consumed;
    ``rollouts`` are trajectories (``id``, ``prompt``, ``response``,
    ``segments`` of (version, n tokens)) whose versions are all at most
    ``len(batches)``. Scoring runs ``rows`` trajectories at a time, so
    that it fits next to the weights. Every batch is padded to ``width``
    tokens and ``batch_rows`` rows, and every scored block to ``rows`` x
    ``width``, so that each program compiles once.
    """
    model = refmodels.load(conf)
    a = model.from_config(conf, attn_precision)
    fwd = partial(model.logits, a)
    o = Optim(lr=lr)
    with jax.default_matmul_precision(precision):
        params = model.init_params(a, seed_key(seed))
        state = {
            "m": jax.tree_util.tree_map(jnp.zeros_like, params),
            "v": jax.tree_util.tree_map(jnp.zeros_like, params),
            "step": jnp.zeros((), jnp.int32),
        }
        init_host = jax.device_get(params)
        step_fn = jax.jit(lambda p, s, b: train_step(fwd, o, p, s, b, dtype))
        lp_fn = jax.jit(lambda p, t: token_logprobs(fwd, p, t))
        by_version: Dict[int, List[Dict[str, Any]]] = {}
        for t in rollouts:
            for v in {v for v, _ in t["segments"]}:
                by_version.setdefault(v, []).append(t)
        scored: Dict[int, np.ndarray] = {
            t["id"]: np.zeros(len(t["response"]), np.float32) for t in rollouts
        }
        losses, ratios, grad_norms = [], [], {}
        for version in range(len(batches) + 1):
            _score(lp_fn, _cast(params, dtype), by_version.get(version, []),
                   version, scored, rows, width)
            if version == len(batches):
                break
            batch = {k: jnp.asarray(v) for k, v in
                     make_batch(batches[version], width, batch_rows).items()}
            # the control keeps float32 master weights, as mixed precision
            # training does: only its passes run in bfloat16
            params, state, loss, ratio, g = step_fn(params, state, batch)
            losses.append(float(loss))
            ratios.append(float(ratio))
            if version == 0:
                grad_norms = leaf_norms(g)
            del g
        change = jax.tree_util.tree_map(
            lambda p, p0: np.asarray(p, np.float64) - np.asarray(p0, np.float64),
            jax.device_get(params), init_host,
        )
    return Readings(losses, ratios, grad_norms, leaf_norms(change), scored)


def _score(lp_fn, params, trajs, version, scored, rows, width):
    """Write the logprob of every token of ``trajs`` that ``version``
    generated into ``scored``."""
    for i in range(0, len(trajs), rows):
        chunk = trajs[i : i + rows]
        seqs = [list(t["prompt"]) + list(t["response"]) for t in chunk]
        width = max([width] + [len(s) for s in seqs])
        tokens = np.zeros((rows, width), np.int32)
        for r, s in enumerate(seqs):
            tokens[r, : len(s)] = s
        lp = np.asarray(lp_fn(params, jnp.asarray(tokens)))
        for r, t in enumerate(chunk):
            n = len(t["prompt"])
            j = 0
            for v, count in t["segments"]:
                if v == version:
                    # response token j sits at n + j, predicted from n + j - 1
                    scored[t["id"]][j : j + count] = lp[r, n - 1 + j :
                                                        n - 1 + j + count]
                j += count
