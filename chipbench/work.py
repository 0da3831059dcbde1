"""Operations and bytes the algorithm needs, from shapes alone.

These count the work, whatever implements it: live K/V rows and valid
tokens, never a pool's size or a bucket's padding. So a kernel's share of
its roofline cannot pass 100% after a later change to the kernel.
A multiply-add counts as two operations.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, Sequence, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"


@dataclass(frozen=True)
class Peak:
    flops_per_s: float
    bytes_per_s: float

    def least_s(self, flops: float, nbytes: float) -> float:
        """The least time the chip could take: the larger of the two
        bounds."""
        return max(flops / self.flops_per_s, nbytes / self.bytes_per_s)


def peak(device_kind: str) -> Peak:
    """The chip's peaks; a device not in the table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    p = table[device_kind]
    return Peak(p["bf16_flops_per_s"], p["hbm_bytes_per_s"])


@dataclass(frozen=True)
class Shape:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int                 # the dense FFN's width, or one expert's
    vocab: int
    n_layers: int
    dtype_bytes: int = 4
    n_experts: int = 0        # experts held on this chip; 0: a dense FFN
    experts_total: int = 0    # the router's width
    top_k: int = 0            # experts per token

    @classmethod
    def from_config(cls, conf: Dict) -> "Shape":
        """The file's shape. An expert model (``num_experts``, the count
        held here) routes over ``reduced.num_experts.published`` experts
        where the file cut them, else over those held, to
        ``num_experts_per_tok`` of width ``moe_intermediate_size``."""
        held = conf.get("num_experts", 0)
        experts = {}
        if held:
            experts = dict(
                n_experts=held, top_k=conf["num_experts_per_tok"],
                experts_total=conf.get("reduced", {}).get(
                    "num_experts", {}).get("published", held))
        return cls(conf["hidden_size"], conf["num_attention_heads"],
                   conf["num_key_value_heads"], conf["head_dim"],
                   conf["moe_intermediate_size" if held else
                        "intermediate_size"], conf["vocab_size"],
                   conf["num_hidden_layers"], **experts)

    @property
    def layer_matmul_params(self) -> float:
        """Weights one token multiplies by in one layer: the attention
        projections, and the dense SwiGLU FFN, or the router and the
        expected expert slots on this chip. Under uniform routing a token's
        ``top_k`` slots land here ``top_k * n_experts / experts_total``
        times, each a SwiGLU of width ``d_ff``; what the tokens actually
        routed here need is not counted."""
        d, qd = self.d_model, self.n_heads * self.head_dim
        kvd = self.n_kv_heads * self.head_dim
        attn = d * qd + 2 * d * kvd + qd * d
        if not self.n_experts:
            return attn + 3 * d * self.d_ff
        slots = self.top_k * self.n_experts / self.experts_total
        return attn + d * self.experts_total + slots * 3 * d * self.d_ff


def paged_decode(s: Shape, lengths: Sequence[int]) -> Tuple[float, float]:
    """One layer's paged decode attention over rows with ``lengths`` valid
    K/V rows each (the new token's included): (flops, bytes). Bytes are
    the query, the live K and V rows and the output."""
    h, hkv, hd, b = s.n_heads, s.n_kv_heads, s.head_dim, s.dtype_bytes
    flops = sum(4.0 * h * hd * n for n in lengths)
    kv = sum(2.0 * n * hkv * hd * b for n in lengths)
    qo = 2.0 * len(lengths) * h * hd * b
    return flops, kv + qo


def prefill_attention(s: Shape, rows: Iterable[Tuple[int, int]]
                      ) -> Tuple[float, float]:
    """One layer's causal prefill attention over rows of
    ``(new tokens, total tokens)``: the new tokens are the last of the
    total and attend to every earlier one (a full prefill has
    new == total). (flops, bytes): the queries and outputs of the new
    tokens and the K and V of all."""
    h, hkv, hd, b = s.n_heads, s.n_kv_heads, s.head_dim, s.dtype_bytes
    flops = nbytes = 0.0
    for new, total in rows:
        start = total - new
        # query i (0-based among the new) attends to start + i + 1 keys
        keys = new * start + new * (new + 1) / 2
        flops += 4.0 * h * hd * keys
        nbytes += 2.0 * new * h * hd * b + 2.0 * total * hkv * hd * b
    return flops, nbytes


def forward_flops(s: Shape, context: int) -> float:
    """Operations of one token's forward pass at ``context`` positions
    (itself included): every matmul (``Shape.layer_matmul_params``), the
    LM head over the file's vocabulary (tied or not, the same
    operations), and causal attention."""
    return (2.0 * (s.n_layers * s.layer_matmul_params + s.d_model * s.vocab)
            + 4.0 * s.n_layers * s.n_heads * s.head_dim * context)


def job_flops(s: Shape, prompt: int, responses: Sequence[int]) -> float:
    """Model operations one group needs: its prompt's forward once, each
    response token's forward once, and the trainer's forward and backward
    (three forwards) over every member's prompt and response. Recomputed
    work (re-prefill after a Pull) and padding do not count."""
    fwd = sum(forward_flops(s, c) for c in range(1, prompt + 1))
    # the prompt's forward yields the first token; each later token needs
    # the forward of the one before it
    dec = sum(forward_flops(s, prompt + j) for r in responses
              for j in range(1, r))
    train = 3.0 * sum(
        fwd + sum(forward_flops(s, prompt + j) for j in range(1, r + 1))
        for r in responses
    )
    return fwd + dec + train
