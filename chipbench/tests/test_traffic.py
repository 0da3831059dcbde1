import json
import random

import pytest

from chipbench import traffic as T

MIXES = ["rlvr"]
SEED = 2**33 + 17          # past 32 bits, as the benchmark's seeds are


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_traffic(name):
    mix = T.load_mix(name)
    a, b = T.Traffic(mix, SEED, 151936), T.Traffic(mix, SEED, 151936)
    for g in range(40):
        assert a.prompt(g) == b.prompt(g)
        assert [a.budget(g, m) for m in range(mix.group_size)] == \
               [b.budget(g, m) for m in range(mix.group_size)]


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_same_strata_other_draws(name):
    mix = T.load_mix(name)
    a, b = T.Traffic(mix, SEED, 151936), T.Traffic(mix, SEED + 1, 151936)
    n = len(mix.prompt_lengths)
    assert [a.prompt(g) for g in range(8)] != [b.prompt(g) for g in range(8)]
    # every block of groups holds every prompt length once, on any seed
    for blk in range(3):
        groups = range(blk * n, (blk + 1) * n)
        assert sorted(a.prompt_len(g) for g in groups) == mix.prompt_lengths
        assert sorted(b.prompt_len(g) for g in groups) == mix.prompt_lengths
    # every block of budget_block groups holds the same budgets on any
    # seed, and every group one from each of the G equal-probability
    # strata of the lognormal; only the order differs by seed
    g, k = mix.group_size, mix.budget_block
    for t in (a, b):
        for blk in range(6):
            got = sorted(t.budget(grp, m) for grp in range(blk * k, (blk + 1) * k)
                         for m in range(g))
            assert got == mix.block_budgets
        for grp in range(50):
            got = sorted(t.budget(grp, m) for m in range(g))
            for i, x in enumerate(got):
                lo = mix.budget_at(max(i / g, 1e-9))
                hi = mix.budget_at(min((i + 1) / g, 1 - 1e-9))
                assert lo <= x <= hi
    draws_a = [[a.budget(grp, m) for m in range(g)] for grp in range(20)]
    draws_b = [[b.budget(grp, m) for m in range(g)] for grp in range(20)]
    assert draws_a != draws_b


def test_budgets_follow_the_capped_lognormal():
    """The budgets' mean and median are the mix's, and the longest member
    of a group (its tail) differs from group to group, over every
    top-stratum budget."""
    mix = T.load_mix("rlvr")
    t = T.Traffic(mix, SEED, 151936)
    draws = [t.budget(grp, m) for grp in range(2000)
             for m in range(mix.group_size)]
    mean = sum(draws) / len(draws)
    # capping at 116 takes about 5% off the lognormal's mean of 23.22
    assert 21.0 < mean < 23.22
    assert sorted(draws)[len(draws) // 2] in (13, 14, 15)   # median 14.1
    assert max(draws) == mix.output_cap
    tails = {t.group_len(grp) for grp in range(50)}
    assert tails == {mix.prompt_min + x for x in mix.top_budgets}
    assert len(tails) == mix.budget_block


def test_budget_keyed_on_seed_group_member():
    mix = T.load_mix("rlvr")
    t = T.Traffic(mix, SEED, 151936)
    # asking in any order gives the same budget for (group, member)
    forward = {(g, m): t.budget(g, m) for g in range(10) for m in range(8)}
    t2 = T.Traffic(mix, SEED, 151936)
    for g, m in reversed(list(forward)):
        assert t2.budget(g, m) == forward[(g, m)]


def test_prompt_ids_in_vocab_and_lengths_in_range():
    mix = T.load_mix("rlvr")
    t = T.Traffic(mix, 5, 1000)
    for g in range(50):
        p = t.prompt(g)
        assert mix.prompt_min <= len(p) <= mix.prompt_max
        assert all(T.FIRST_ID <= x < 1000 for x in p)


def test_new_traffic_file_found_by_name(tmp_path):
    raw = json.loads((T.TRAFFIC_DIR / "rlvr.json").read_text())
    raw["prompt_min"], raw["prompt_max"] = 100, 110
    (tmp_path / "brand-new.json").write_text(json.dumps(raw))
    mix = T.load_mix("brand-new", tmp_path)
    assert mix.name == "brand-new" and mix.prompt_lengths[0] == 100
    assert 100 <= len(T.Traffic(mix, 1, 1000).prompt(0)) <= 110


@pytest.mark.parametrize("name", MIXES)
def test_warmed_lengths_cover_drawn_batches(name):
    """The train step is warmed at every T of ``train_lengths``: at least
    99.9% of batches drawn from the traffic (any groups together, as the
    coordinator consumes whichever finish first) must land on one."""
    mix = T.load_mix(name)
    t = T.Traffic(mix, SEED, 151936)
    warmed = set(t.train_lengths())
    rng = random.Random(0)
    draws = [t.batch_len(rng.sample(range(500), mix.batch_size))
             for _ in range(5000)]
    covered = sum(1 for x in draws if x in warmed) / len(draws)
    assert covered >= 0.999
    assert max(warmed) <= mix.max_len
    # the range is the mix's alone: every seed warms the same shapes
    assert t.train_lengths() == T.Traffic(mix, 3, 151936).train_lengths()
