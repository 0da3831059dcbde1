"""The plain reference: a dense GQA transformer, its DAPO loss and AdamW,
in straightforward ``jax.numpy``.

It imports nothing of the program and takes nothing the program made. Its
weights come from the seed by the same draws the program's initializer
makes (a seeded normal per matrix, scaled by 1/sqrt(fan in); norms 1,
biases 0), so at version 0 both hold the same numbers without one handing
them to the other; after that the reference follows the training steps
itself, from the token batches the job consumed.

The layer equations, as the configuration file states them: token
embedding (tied LM head); per layer pre-RMSNorm, q/k/v projections (with
bias where ``qkv_bias``), rotary embedding on q and k (half-split
rotation, base ``rope_theta``), causal softmax attention with grouped K/V
heads and scale 1/sqrt(head_dim), output projection, residual;
pre-RMSNorm SwiGLU MLP, residual; final RMSNorm and the LM head. The
vocabulary is padded to a multiple of 256 rows and the padded logit
columns are masked, as in the program.

``dtype`` and ``precision`` pick how it computes. The reference is
float32 at the matmul precision the configuration states; on a TPU,
``default`` rounds each matmul's inputs to bfloat16. The control is the
step below float32: the same passes in bfloat16 throughout, on float32
master weights. ``attn_precision`` sets the precision of the two attention
products alone.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


@dataclass(frozen=True)
class Arch:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    n_layers: int
    rope_theta: float
    norm_eps: float
    qkv_bias: bool
    # precision of the attention products (q.k and p.v); None follows the
    # ambient matmul precision
    attn_precision: Optional[str] = None

    @classmethod
    def from_config(cls, conf: Dict[str, Any]) -> "Arch":
        if not conf.get("tie_word_embeddings", False):
            raise ValueError("the reference covers tied embeddings only")
        return cls(
            d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
            n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
            d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
            n_layers=conf["num_hidden_layers"], rope_theta=conf["rope_theta"],
            norm_eps=conf["rms_norm_eps"], qkv_bias=conf["qkv_bias"],
        )

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab_size // 256) * 256


# ------------------------------------------------------------------ weights
def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))


def _layer(a: Arch, key) -> Params:
    k_norm1, k_attn, k_norm2, k_ffn = jax.random.split(key, 4)
    d, h, hkv, hd, f = a.d_model, a.n_heads, a.n_kv_heads, a.head_dim, a.d_ff
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    kg, ku, kd = jax.random.split(k_ffn, 3)
    p = {
        "attn_norm": jnp.ones((d,)),
        "wq": _normal(kq, (d, h * hd), d),
        "wk": _normal(kk, (d, hkv * hd), d),
        "wv": _normal(kv, (d, hkv * hd), d),
        "wo": _normal(ko, (h * hd, d), h * hd),
        "ffn_norm": jnp.ones((d,)),
        "w_gate": _normal(kg, (d, f), d),
        "w_up": _normal(ku, (d, f), d),
        "w_down": _normal(kd, (f, d), f),
    }
    if a.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,))
        p["bk"] = jnp.zeros((hkv * hd,))
        p["bv"] = jnp.zeros((hkv * hd,))
    return p


@partial(jax.jit, static_argnums=0)
def init_params(a: Arch, seed_key) -> Params:
    """Float32 weights from the seed's key, made on the device in one call."""
    ks = jax.random.split(seed_key, 8)
    layer_keys = jnp.stack(jax.random.split(ks[3], a.n_layers))
    return {
        "embed": _normal(ks[0], (a.padded_vocab, a.d_model), a.d_model),
        "final_norm": jnp.ones((a.d_model,)),
        "blocks": jax.vmap(partial(_layer, a))(layer_keys),
    }


def seed_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(seed)


# ------------------------------------------------------------------ forward
def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x: (B, S, H, hd), positions 0..S-1, half-split rotation."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs      # (S, hd/2)
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _block(a: Arch, x, p):
    b, s, _ = x.shape
    h, hkv, hd = a.n_heads, a.n_kv_heads, a.head_dim
    mm = lambda u, w: jnp.einsum("bsd,de->bse", u, w)
    y = _rms_norm(x, p["attn_norm"], a.norm_eps)
    q, k, v = mm(y, p["wq"]), mm(y, p["wk"]), mm(y, p["wv"])
    if a.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _rope(q.reshape(b, s, h, hd), a.rope_theta)
    k = _rope(k.reshape(b, s, hkv, hd), a.rope_theta)
    v = v.reshape(b, s, hkv, hd)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=a.attn_precision).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                   precision=a.attn_precision).reshape(b, s, h * hd)
    x = x + mm(o, p["wo"])
    y = _rms_norm(x, p["ffn_norm"], a.norm_eps)
    return x + mm(jax.nn.silu(mm(y, p["w_gate"])) * mm(y, p["w_up"]), p["w_down"])


def logits(a: Arch, params: Params, tokens):
    """(B, S) tokens -> (B, S, padded vocab) float32 logits."""
    x = params["embed"][tokens]
    for i in range(a.n_layers):
        x = _block(a, x, jax.tree_util.tree_map(lambda l: l[i], params["blocks"]))
    x = _rms_norm(x, params["final_norm"], a.norm_eps)
    out = jnp.einsum("bsd,vd->bsv", x, params["embed"]).astype(jnp.float32)
    col = jnp.arange(out.shape[-1])
    return jnp.where(col < a.vocab_size, out, -1e9)


def token_logprobs(a: Arch, params: Params, tokens):
    """log p(tokens[:, t+1] | tokens[:, :t+1]) for t < S-1: (B, S-1)."""
    lg = logits(a, params, tokens)[:, :-1]
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


def dapo_loss(a: Arch, o: Optim, params, batch):
    """Token-mean clipped policy gradient over the response tokens, and the
    mean importance ratio against the behaviour logprobs."""
    lp = token_logprobs(a, params, batch["tokens"])
    blp = batch["behavior_logprobs"][:, 1:]
    m = batch["mask"][:, 1:]
    adv = batch["advantages"][:, None]
    ratio = jnp.exp(lp - blp)
    obj = jnp.minimum(ratio * adv,
                      jnp.clip(ratio, 1 - o.eps_low, 1 + o.eps_high) * adv)
    denom = jnp.maximum(m.sum(), 1.0)
    return -(obj * m).sum() / denom, (ratio * m).sum() / denom


def train_step(a: Arch, o: Optim, params, state, batch, dtype=jnp.float32):
    """One DAPO step with global-norm clipping and AdamW (no decay) on
    float32 weights; the forward and backward passes run in ``dtype``.
    Returns (params, state, loss, mean ratio, clipped gradient)."""
    (loss, ratio), g = jax.value_and_grad(
        lambda p: dapo_loss(a, o, _cast(p, dtype), batch), has_aux=True
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
    a = dataclasses.replace(Arch.from_config(conf),
                            attn_precision=attn_precision)
    o = Optim(lr=lr)
    with jax.default_matmul_precision(precision):
        params = init_params(a, seed_key(seed))
        state = {
            "m": jax.tree_util.tree_map(jnp.zeros_like, params),
            "v": jax.tree_util.tree_map(jnp.zeros_like, params),
            "step": jnp.zeros((), jnp.int32),
        }
        init_host = jax.device_get(params)
        step_fn = jax.jit(lambda p, s, b: train_step(a, o, p, s, b, dtype))
        lp_fn = jax.jit(lambda p, t: token_logprobs(a, p, t))
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
