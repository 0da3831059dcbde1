"""The one traffic generator: reads a mix file and draws a run's work from
the seed.

A mix (``chipbench/traffic/<name>.json``) names the sizes of one RL job:
groups per step, members per group, the range of prompt lengths, the
lognormal of output budgets and the runtime sizes that serve them. The
seed draws the token ids and the sizes; every seed draws its sizes from
the same strata, so runs of different seeds do about the same work:

* prompt lengths are stratified over blocks of groups: each block of
  ``hi - lo + 1`` consecutive groups takes every length in ``[lo, hi]``
  once, in an order drawn from ``(seed, block)``;
* a member's output budget is a draw from the capped lognormal, keyed on
  ``(seed, group, member)``, stratified within its group: the ``G``
  members of a group take one draw each from the ``G`` equal-probability
  strata of the lognormal, in an order drawn from ``(seed, group)``. So
  each group holds a few short answers and one from the top stratum, whose
  length (and the group's tail) differs from group to group.

The budget stands in for an EOS, which random weights emit only by
chance; the harness turns that chance off. The coordinator never sees the
budget; only the engine does, from the trajectory it admits.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
# first token id a prompt may use: ids 0-2 are the tokenizer's specials
FIRST_ID = 3


@dataclass(frozen=True)
class Mix:
    name: str
    batch_size: int            # groups consumed per RL step
    group_size: int            # members per group
    prompt_min: int
    prompt_max: int
    output_mean: float         # lognormal mean of the output budget
    output_sigma: float
    output_cap: int
    eta: int
    n_instances: int
    max_slots: int
    max_len: int
    decode_steps_per_tick: int
    kv_block_size: int
    lr: float
    temperature: float
    budget_block: int = 8      # groups that share out one set of budgets

    def budget_at(self, u: float) -> int:
        """The capped lognormal's quantile at ``u`` in (0, 1), in tokens."""
        mu = math.log(self.output_mean) - self.output_sigma ** 2 / 2
        b = math.exp(mu + self.output_sigma * NormalDist().inv_cdf(u))
        return max(1, min(self.output_cap, int(round(b))))

    def stratum_budget(self, stratum: int, fine: int) -> int:
        """The budget at the midpoint of fine stratum ``fine`` (of
        ``budget_block``) of coarse stratum ``stratum`` (of
        ``group_size``)."""
        k = self.budget_block
        return self.budget_at((stratum + (fine + 0.5) / k) / self.group_size)

    @property
    def block_budgets(self) -> List[int]:
        """The budgets every block of ``budget_block`` groups holds."""
        return sorted(self.stratum_budget(s, f)
                      for s in range(self.group_size)
                      for f in range(self.budget_block))

    @property
    def top_budgets(self) -> List[int]:
        """The budgets a group's top-stratum member can take."""
        return sorted({self.stratum_budget(self.group_size - 1, f)
                       for f in range(self.budget_block)})

    @property
    def prompt_lengths(self) -> List[int]:
        return list(range(self.prompt_min, self.prompt_max + 1))


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> Mix:
    """The mix in ``<directory>/<name>.json``; found by name alone."""
    path = directory / f"{name}.json"
    raw = json.loads(path.read_text())
    for note in ("why", "source", "assumed"):
        raw.pop(note, None)
    return Mix(name=name, **raw)


def _rng(seed: int, *keys: int) -> np.random.Generator:
    # SeedSequence takes any non-negative ints, so seeds past 2**32 are fine
    return np.random.default_rng(np.random.SeedSequence([seed, *keys]))


class Traffic:
    """One run's prompts and budgets, a pure function of (mix, seed)."""

    def __init__(self, mix: Mix, seed: int, vocab_size: int):
        if seed < 0:
            raise ValueError(f"seed must be >= 0, got {seed}")
        self.mix = mix
        self.seed = seed
        self.vocab_size = vocab_size
        self._lengths: Dict[int, List[int]] = {}

    def prompt_len(self, group: int) -> int:
        lengths = self.mix.prompt_lengths
        block, i = divmod(group, len(lengths))
        order = self._lengths.get(block)
        if order is None:
            order = list(_rng(self.seed, 1, block).permutation(lengths))
            self._lengths[block] = order
        return int(order[i])

    def prompt(self, group: int) -> List[int]:
        n = self.prompt_len(group)
        ids = _rng(self.seed, 2, group).integers(
            FIRST_ID, self.vocab_size, size=n
        )
        return [int(t) for t in ids]

    def budget(self, group: int, member: int) -> int:
        g, k = self.mix.group_size, self.mix.budget_block
        stratum = int(_rng(self.seed, 3, group).permutation(g)[member])
        block, i = divmod(group, k)
        fine = int(_rng(self.seed, 5, block, stratum).permutation(k)[i])
        return self.mix.stratum_budget(stratum, fine)

    def prompts(self, first_group: int = 0) -> Iterator[List[int]]:
        """Prompts of groups ``first_group``, ``first_group + 1``, ..."""
        g = first_group
        while True:
            yield self.prompt(g)
            g += 1

    def group_len(self, group: int) -> int:
        """The group's longest member, prompt plus response."""
        return self.prompt_len(group) + max(
            self.budget(group, m) for m in range(self.mix.group_size))

    def batch_len(self, groups: List[int]) -> int:
        """Padded length ``T`` of a train batch of these groups: the
        trainer pads to the longest member, prompt plus response."""
        return max(self.group_len(g) for g in groups)

    def train_lengths(self) -> List[int]:
        """Every ``T`` a train batch can take, on any seed: a prompt length
        plus a top-stratum budget (a group's longest member is its
        top-stratum one), and one either side of it. The coordinator
        consumes whichever groups finish first, so any groups can share a
        batch. The program returns a response one token over its budget
        where a token is sampled at admission and again by the first decode
        (a re-prefill after a Pull, a budget of 1), and one short where it
        reaches ``max_len``; a length outside these would compile a train
        step inside the window."""
        return sorted({min(p + b + d, self.mix.max_len)
                       for p in self.mix.prompt_lengths
                       for b in self.mix.top_budgets
                       for d in (-1, 0, 1)})
