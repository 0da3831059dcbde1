"""A dense GQA transformer in straightforward ``jax.numpy``.

Its weights come from the seed by the same draws the program's
initializer makes (a seeded normal per matrix, scaled by 1/sqrt(fan in);
norms 1, biases 0), so at version 0 both hold the same numbers without
one handing them to the other.

The layer equations, as the configuration file states them: token
embedding; per layer pre-RMSNorm, q/k/v projections (with bias where
``qkv_bias``), rotary embedding on q and k (half-split rotation, base
``rope_theta``), causal softmax attention with grouped K/V heads and
scale 1/sqrt(head_dim), output projection, residual; pre-RMSNorm SwiGLU
MLP, residual; final RMSNorm and the LM head: the embedding's transpose
where ``tie_word_embeddings``, else a matrix of its own (``lm_head``). The
vocabulary is padded to a multiple of 256 rows and the padded logit
columns are masked, as in the program.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

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
    tie_embeddings: bool
    # precision of the attention products (q.k and p.v); None follows the
    # ambient matmul precision
    attn_precision: Optional[str] = None

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab_size // 256) * 256


def from_config(conf: Dict[str, Any], attn_precision: Optional[str] = None
                ) -> Arch:
    return Arch(
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        n_layers=conf["num_hidden_layers"], rope_theta=conf["rope_theta"],
        norm_eps=conf["rms_norm_eps"], qkv_bias=conf["qkv_bias"],
        tie_embeddings=conf["tie_word_embeddings"],
        attn_precision=attn_precision,
    )


# ------------------------------------------------------------------ weights
def normal(key, shape, fan_in):
    return jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))


def _layer(a: Arch, key) -> Params:
    k_norm1, k_attn, k_norm2, k_ffn = jax.random.split(key, 4)
    d, h, hkv, hd, f = a.d_model, a.n_heads, a.n_kv_heads, a.head_dim, a.d_ff
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    kg, ku, kd = jax.random.split(k_ffn, 3)
    p = {
        "attn_norm": jnp.ones((d,)),
        "wq": normal(kq, (d, h * hd), d),
        "wk": normal(kk, (d, hkv * hd), d),
        "wv": normal(kv, (d, hkv * hd), d),
        "wo": normal(ko, (h * hd, d), h * hd),
        "ffn_norm": jnp.ones((d,)),
        "w_gate": normal(kg, (d, f), d),
        "w_up": normal(ku, (d, f), d),
        "w_down": normal(kd, (f, d), f),
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
    params = {
        "embed": normal(ks[0], (a.padded_vocab, a.d_model), a.d_model),
        "final_norm": jnp.ones((a.d_model,)),
        "blocks": jax.vmap(partial(_layer, a))(layer_keys),
    }
    if not a.tie_embeddings:
        params["lm_head"] = normal(ks[2], (a.d_model, a.padded_vocab),
                                   a.d_model)
    return params


# ------------------------------------------------------------------ forward
def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta):
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
    y = rms_norm(x, p["attn_norm"], a.norm_eps)
    q, k, v = mm(y, p["wq"]), mm(y, p["wk"]), mm(y, p["wv"])
    if a.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q.reshape(b, s, h, hd), a.rope_theta)
    k = rope(k.reshape(b, s, hkv, hd), a.rope_theta)
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
    y = rms_norm(x, p["ffn_norm"], a.norm_eps)
    return x + mm(jax.nn.silu(mm(y, p["w_gate"])) * mm(y, p["w_up"]), p["w_down"])


def logits(a: Arch, params: Params, tokens):
    """(B, S) tokens -> (B, S, padded vocab) float32 logits."""
    x = params["embed"][tokens]
    for i in range(a.n_layers):
        x = _block(a, x, jax.tree_util.tree_map(lambda l: l[i], params["blocks"]))
    x = rms_norm(x, params["final_norm"], a.norm_eps)
    if a.tie_embeddings:
        out = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        out = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    out = out.astype(jnp.float32)
    col = jnp.arange(out.shape[-1])
    return jnp.where(col < a.vocab_size, out, -1e9)
