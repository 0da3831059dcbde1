"""Equivalence: the batched PrefillRunner / compacted DecodeRunner must
reproduce the seed engine's single-row path.

The seed path is still constructible (``batched_prefill=False`` +
``compact_decode=False`` forces single-row prefill groups and full
``max_slots`` decode), so every test runs the same workload through both
configurations and compares tokens exactly and behavior logprobs within
``LOGPROB_ATOL`` — greedy decoding makes token selection key-independent,
and batched matmul rows agree with single rows to float32 rounding (XLA
may group a row's sums differently for a different batch shape)."""
import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.types import Trajectory, reset_traj_ids
from repro.models import model as M
from repro.rollout.engine import RolloutInstance
from repro.rollout import runners

CFG = get_arch("qwen2-1.5b").reduced()
PARAMS = M.init_params(CFG, jax.random.PRNGKey(0))
# Tolerance for behavior logprobs of two paths that run the same rows in
# batches of different shapes (batched vs single-row prefill, compaction
# buckets, suffix-prefill forks, a busier migration destination): XLA:CPU
# reassociates float32 sums by batch shape (and thread count), so logprobs
# drift by ~1 ulp (up to 1.2e-6 seen). Tokens are still compared exactly.
LOGPROB_ATOL = 1e-5


def mk_traj(tid, prompt_len=6, max_new=16):
    prompt = list(np.random.RandomState(tid).randint(3, 17, size=prompt_len))
    return Trajectory(traj_id=tid, prompt=prompt, max_new_tokens=max_new)


def mk_inst(*, legacy: bool, slots=4, max_len=64, seed=0, **kw):
    return RolloutInstance(
        0, CFG, PARAMS, 0, max_slots=slots, max_len=max_len,
        temperature=0.0, seed=seed,
        batched_prefill=not legacy, compact_decode=not legacy, **kw,
    )


def run_workload(inst, trajs, steps=60):
    """Route everything up front, then decode until all complete."""
    for t in trajs:
        inst.route(t)
    done = []
    for _ in range(steps):
        done.extend(inst.step())
        if len(done) == len(trajs):
            break
    return done


def assert_same_streams(trajs_a, trajs_b, *, atol=LOGPROB_ATOL):
    """Tokens equal exactly; behavior logprobs equal within ``atol``."""
    for ta, tb in zip(trajs_a, trajs_b):
        assert ta.traj_id == tb.traj_id
        assert ta.response == tb.response, (
            f"traj {ta.traj_id}: batched {ta.response} != seed {tb.response}"
        )
        a = np.asarray(ta.behavior_logprobs)
        b = np.asarray(tb.behavior_logprobs)
        np.testing.assert_allclose(
            a, b, rtol=0, atol=atol,
            err_msg=f"traj {ta.traj_id} behavior logprobs diverge",
        )


@pytest.mark.parametrize("n_trajs,prompt_lens", [
    (3, (6, 6, 6)),            # one shared bucket -> one batched forward
    (4, (5, 21, 9, 17)),       # two buckets (16/32) -> grouped forwards
    (6, (6, 7, 8, 9, 10, 11)), # more trajs than slots -> waiting queue
])
def test_batched_prefill_and_compact_decode_match_seed(n_trajs, prompt_lens):
    reset_traj_ids()
    mk = lambda: [
        mk_traj(100 + i, prompt_len=pl, max_new=10)
        for i, pl in enumerate(prompt_lens)
    ]
    done_new = run_workload(mk_inst(legacy=False), mk())
    done_seed = run_workload(mk_inst(legacy=True), mk())
    assert len(done_new) == len(done_seed) == n_trajs
    key = lambda t: t.traj_id
    assert_same_streams(sorted(done_new, key=key), sorted(done_seed, key=key))


def test_single_active_slot_decode_matches_seed():
    """1 active of 4 slots: the compact path decodes a 1-row bucket while
    the seed path decodes all 4 rows — same tokens, same logprobs."""
    reset_traj_ids()
    t_new, t_seed = mk_traj(7, max_new=12), mk_traj(7, max_new=12)
    inst_new, inst_seed = mk_inst(legacy=False), mk_inst(legacy=True)
    run_workload(inst_new, [t_new])
    run_workload(inst_seed, [t_seed])
    assert t_new.finished and t_seed.finished
    assert_same_streams([t_new], [t_seed])
    # and the compact path really did decode fewer rows' worth of work
    assert inst_new.decode_tokens == inst_seed.decode_tokens


def test_interrupt_migrate_reprefill_matches_seed():
    """Partial rollout: interrupt mid-stream, migrate, re-prefill (the
    batched path re-prefills prompt+partial response like the seed)."""
    reset_traj_ids()

    def migrate(legacy):
        t = mk_traj(11, max_new=12)
        a = mk_inst(legacy=legacy)
        b = mk_inst(legacy=legacy)
        a.route(t)
        for _ in range(4):
            a.step()
        a.interrupt([t.traj_id])
        b.route(t)
        for _ in range(60):
            if t.finished:
                break
            b.step()
        return t

    assert_same_streams([migrate(False)], [migrate(True)])


def test_kv_budget_admission_decisions_match_seed():
    """Batched admission must make the same admit/defer decisions the seed
    slot-scan made under a tight KV budget."""
    reset_traj_ids()
    k5 = 2 * CFG.n_layers * CFG.n_kv_heads * CFG.hd * 4
    budget = k5 * 40  # room for ~2 short trajectories, not 4
    mk = lambda: [mk_traj(200 + i, prompt_len=8, max_new=6) for i in range(4)]

    def admit_sets(legacy):
        inst = mk_inst(legacy=legacy, kv_budget=budget)
        for t in mk():
            inst.route(t)
        s = inst.snapshot()
        return s.run_trajs, s.wait_trajs

    assert admit_sets(False) == admit_sets(True)


def test_cache_rows_bitwise_identical_after_batched_prefill():
    """The fused multi-row scatter writes exactly what the per-row
    tree_map scatter wrote."""
    reset_traj_ids()
    mk = lambda: [mk_traj(300 + i, prompt_len=6, max_new=8) for i in range(3)]
    inst_new, inst_seed = mk_inst(legacy=False), mk_inst(legacy=True)
    for t in mk():
        inst_new.route(t)
    for t in mk():
        inst_seed.route(t)
    for name in inst_new.cache:
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"cache[{name}]"
            ),
            inst_new.cache[name],
            inst_seed.cache[name],
        )
    np.testing.assert_array_equal(
        np.asarray(inst_new._last_tokens), np.asarray(inst_seed._last_tokens)
    )


def test_route_many_wave_matches_sequential_seed_routes():
    """A route_many wave (the executor's coalesced form) must produce the
    same streams as the seed's one-route()-at-a-time admission."""
    reset_traj_ids()
    mk = lambda: [mk_traj(500 + i, prompt_len=6 + i, max_new=8) for i in range(4)]

    inst_new = mk_inst(legacy=False)
    wave = mk()
    inst_new.route_many(wave)
    done_new = []
    for _ in range(60):
        done_new.extend(inst_new.step())
        if len(done_new) == 4:
            break

    done_seed = run_workload(mk_inst(legacy=True), mk())
    key = lambda t: t.traj_id
    assert_same_streams(sorted(done_new, key=key), sorted(done_seed, key=key))


def test_stochastic_prefill_sampling_matches_seed():
    """Prefill sampling keys are split per trajectory (seed order), so even
    stochastic (temperature=1) first tokens match the seed path bitwise —
    the vmapped per-row sampler must equal the per-row sample() loop."""
    reset_traj_ids()

    def first_tokens(legacy):
        inst = RolloutInstance(
            0, CFG, PARAMS, 0, max_slots=4, max_len=64, temperature=1.0,
            seed=3, batched_prefill=not legacy, compact_decode=not legacy,
        )
        trajs = [mk_traj(400 + i, prompt_len=5 + i) for i in range(4)]
        for t in trajs:
            inst.route(t)
        return [(t.response[0], t.behavior_logprobs[0]) for t in trajs]

    assert first_tokens(False) == first_tokens(True)


def test_gather_scatter_roundtrip_identity():
    cache = M.init_cache(CFG, 4, 32)
    cache = {k: jax.tree_util.tree_map(
        lambda a: a + np.float32(1.5) if a.dtype != np.int32 else a + 1, v)
        for k, v in cache.items()}
    rows = jax.numpy.asarray([1, 3])
    sub = runners.gather_rows(cache, rows)
    back = runners.scatter_rows(cache, sub, rows)
    for name in cache:
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            cache[name],
            back[name],
        )


def test_decode_bucket_sizes():
    r = runners.DecodeRunner(CFG, max_slots=8)
    assert r.bucket_of(1) == 1
    assert r.bucket_of(2) == 2
    assert r.bucket_of(3) == 4
    assert r.bucket_of(5) == 8
    assert r.bucket_of(8) == 8


# ===================================================== paged KV equivalence
# The block-paged cache (shared pool + per-trajectory block tables) must be
# bit-for-bit equivalent to the dense per-slot layout under greedy decoding:
# valid cache lanes hold identical values and masked lanes contribute exact
# zeros, so tokens AND behavior logprobs match — including across slot
# reuse, interrupt/migrate re-prefill, and KV-budget admission.

def mk_paged(*, slots=4, max_len=64, seed=0, block_size=16, **kw):
    return RolloutInstance(
        0, CFG, PARAMS, 0, max_slots=slots, max_len=max_len,
        temperature=0.0, seed=seed, paged=True, kv_block_size=block_size,
        **kw,
    )


@pytest.mark.parametrize("n_trajs,prompt_lens", [
    (3, (6, 6, 6)),            # one shared bucket
    (4, (5, 21, 9, 17)),       # two prefill buckets
    (6, (6, 7, 8, 9, 10, 11)), # slot reuse through the waiting queue
])
def test_paged_decode_matches_dense(n_trajs, prompt_lens):
    reset_traj_ids()
    mk = lambda: [
        mk_traj(600 + i, prompt_len=pl, max_new=10)
        for i, pl in enumerate(prompt_lens)
    ]
    done_paged = run_workload(mk_paged(), mk())
    done_dense = run_workload(mk_inst(legacy=False), mk())
    assert len(done_paged) == len(done_dense) == n_trajs
    key = lambda t: t.traj_id
    assert_same_streams(
        sorted(done_paged, key=key), sorted(done_dense, key=key)
    )


@pytest.mark.parametrize("block_size", [8, 16, 32, 64])
def test_paged_block_size_sweep_matches_dense(block_size):
    reset_traj_ids()
    mk = lambda: [mk_traj(700 + i, prompt_len=6 + i, max_new=8) for i in range(3)]
    done_paged = run_workload(mk_paged(block_size=block_size), mk())
    done_dense = run_workload(mk_inst(legacy=False), mk())
    key = lambda t: t.traj_id
    assert_same_streams(
        sorted(done_paged, key=key), sorted(done_dense, key=key)
    )


def test_paged_interrupt_migrate_reprefill_matches_dense():
    """Partial rollout across instances: blocks are freed at interrupt and
    reallocated at re-prefill on the destination replica."""
    reset_traj_ids()

    def migrate(paged):
        t = mk_traj(11, max_new=12)
        a = mk_paged() if paged else mk_inst(legacy=False)
        b = mk_paged() if paged else mk_inst(legacy=False)
        a.route(t)
        for _ in range(4):
            a.step()
        a.interrupt([t.traj_id])
        if paged:
            a.allocator.check()
            assert a.allocator.used_blocks == 0
        b.route(t)
        for _ in range(60):
            if t.finished:
                break
            b.step()
        return t

    assert_same_streams([migrate(True)], [migrate(False)])


def test_paged_preemption_on_block_exhaustion():
    """A pool too small for all residents preempts the youngest trajectory
    back to the waiting queue; greedy token streams still match dense and
    no block leaks."""
    reset_traj_ids()
    NO_EOS = -1

    def run(paged):
        if paged:
            # 9 blocks x 8 tokens = 72 token capacity for 3 trajectories
            # growing to ~35 tokens each -> exhaustion mid-decode
            inst = RolloutInstance(
                0, CFG, PARAMS, 0, max_slots=3, max_len=64,
                temperature=0.0, seed=0, eos_id=NO_EOS,
                paged=True, kv_block_size=8, kv_pool_blocks=9,
            )
        else:
            inst = RolloutInstance(
                0, CFG, PARAMS, 0, max_slots=3, max_len=64,
                temperature=0.0, seed=0, eos_id=NO_EOS,
            )
        trajs = [mk_traj(800 + i, prompt_len=5 + i, max_new=30) for i in range(3)]
        for t in trajs:
            inst.route(t)
        done = []
        for _ in range(400):
            done.extend(inst.step())
            if inst.allocator is not None:
                inst.allocator.check()
            if len(done) == 3:
                break
        return inst, sorted(done, key=lambda t: t.traj_id)

    inst_p, done_p = run(True)
    inst_d, done_d = run(False)
    assert inst_p.preemptions > 0
    assert len(done_p) == len(done_d) == 3
    for a, b in zip(done_p, done_d):
        assert a.traj_id == b.traj_id
        assert a.response == b.response
    assert inst_p.allocator.used_blocks == 0
    inst_p.allocator.check()


def test_paged_admits_within_block_budget():
    """Admission charges actual allocated blocks against the budget."""
    reset_traj_ids()
    k5 = 2 * CFG.n_layers * CFG.n_kv_heads * CFG.hd * 4
    bs = 16
    budget = k5 * bs * 3  # room for exactly 3 blocks
    inst = mk_paged(block_size=bs, kv_budget=budget, slots=4)
    for i in range(4):
        inst.route(mk_traj(900 + i, prompt_len=6, max_new=6))
    s = inst.snapshot()
    # each short trajectory occupies one block but is charged headroom
    # (6 + 16 tokens -> 2 blocks) at the admission decision
    assert len(s.run_trajs) == 2
    assert s.kv_cache == k5 * bs * 2
    assert inst.kv_bytes() == k5 * inst.allocator.used_tokens()


def test_dense_incremental_kv_counter_stays_exact():
    """The O(1) admission counter must track the O(slots) recomputation
    through admission, decode, completion, and interrupts."""
    reset_traj_ids()
    inst = mk_inst(legacy=False)
    trajs = [mk_traj(950 + i, prompt_len=6 + i, max_new=8) for i in range(6)]
    for t in trajs:
        inst.route(t)
        assert inst.kv_bytes() == inst._recompute_kv_bytes()
    for _ in range(20):
        inst.step()
        assert inst.kv_bytes() == inst._recompute_kv_bytes()
    resident = [t.traj_id for t in inst.slots if t is not None][:2]
    inst.interrupt(resident)
    assert inst.kv_bytes() == inst._recompute_kv_bytes()


# ==================================================== prefix-shared groups
# Group sampling (GRPO/DAPO): group members share one prompt. With
# share_prefix=True the paged engine prefills the prompt ONCE, maps its
# full blocks read-only into every member's table (refcounted), and
# CoW-copies the partial tail block per member. Greedy decode must be
# bit-for-bit equal to group_size independent prefills — including after
# CoW, preemption, and re-admission.

def mk_group(base, n, prompt_len=21, max_new=10, gid=0, seed=1234):
    prompt = list(np.random.RandomState(seed).randint(3, 17, size=prompt_len))
    return [
        Trajectory(traj_id=base + i, prompt=list(prompt), group_id=gid,
                   max_new_tokens=max_new)
        for i in range(n)
    ]


def mk_sharing(*, share: bool, slots=4, max_len=64, block_size=16, **kw):
    return RolloutInstance(
        0, CFG, PARAMS, 0, max_slots=slots, max_len=max_len,
        temperature=0.0, seed=0, paged=True, kv_block_size=block_size,
        share_prefix=share, **kw,
    )


@pytest.mark.parametrize("prompt_len", [21, 32, 7])   # CoW tail / aligned / sub-block
def test_group_shared_prefix_matches_independent(prompt_len):
    """group_size=4 off one shared prefix == 4 independent prefills,
    exactly (tokens + behavior logprobs), while allocating the prompt's
    full blocks once and prefilling the prompt once."""
    reset_traj_ids()
    bs = 16
    done_s = run_workload(
        mk_sharing(share=True, block_size=bs),
        mk_group(1000, 4, prompt_len=prompt_len),
    )
    done_i = run_workload(
        mk_sharing(share=False, block_size=bs),
        mk_group(1000, 4, prompt_len=prompt_len),
    )
    assert len(done_s) == len(done_i) == 4
    key = lambda t: t.traj_id
    assert_same_streams(sorted(done_s, key=key), sorted(done_i, key=key))


def test_group_admission_allocates_prefix_blocks_once():
    """Acceptance: a group of G members over a P-token prompt allocates
    blocks_for(P) blocks for the whole group exactly once — full blocks
    refcounted G ways and (lazy CoW, the default) ONE shared tail block
    that members only copy at first divergence — and prefills P tokens
    once. The first decode step diverges every member: the tail is then
    copied per member (minus the last owner, who writes in place)."""
    reset_traj_ids()
    bs, P, G = 16, 37, 4                  # 2 full blocks + 5-token tail
    inst = mk_sharing(share=True, slots=G, block_size=bs)
    inst.route_many(mk_group(1100, G, prompt_len=P))
    n_full, tail = divmod(P, bs)
    assert inst.n_active() == G
    assert inst.allocator.used_blocks == n_full + (1 if tail else 0)
    assert inst.allocator.shared_blocks == n_full + (1 if tail else 0)
    assert inst.prefill_tokens == P       # one pass over the prompt
    assert inst.shared_prefix_hits == G - 1
    assert inst.prefill_tokens_saved == (G - 1) * P
    assert inst.kv_bytes() == inst.k5 * bs * (n_full + 1)
    assert inst.block_copies == 0         # nobody diverged yet
    inst.allocator.check()
    inst.step()                           # first decode write: divergence
    assert inst.block_copies == G - 1     # last owner wrote in place
    assert inst.allocator.used_blocks == n_full + G
    assert inst.kv_bytes() == inst.k5 * bs * (n_full + G)
    inst.allocator.check()


def test_group_admission_eager_cow_allocates_tails_up_front():
    """lazy_cow=False restores the eager PR-3 behavior: the partial tail
    is copied into a private block per member at admission."""
    reset_traj_ids()
    bs, P, G = 16, 37, 4
    inst = mk_sharing(share=True, slots=G, block_size=bs, lazy_cow=False)
    inst.route_many(mk_group(1100, G, prompt_len=P))
    n_full = P // bs
    assert inst.allocator.used_blocks == n_full + G
    assert inst.allocator.shared_blocks == n_full
    assert inst.block_copies == G - 1     # eager tail copies at admission
    assert inst.kv_bytes() == inst.k5 * bs * (n_full + G)
    inst.step()
    assert inst.block_copies == G - 1     # no further copies at decode
    inst.allocator.check()


def test_group_stochastic_decode_diverges_and_matches_independent():
    """temperature=1: members sample different responses (CoW tails and
    private response blocks really diverge), and the shared path still
    matches the independent path bitwise — same slot layout, same key
    sequence, identical logits rows."""
    reset_traj_ids()

    def run(share):
        inst = RolloutInstance(
            0, CFG, PARAMS, 0, max_slots=4, max_len=64, temperature=1.0,
            seed=11, paged=True, kv_block_size=16, share_prefix=share,
        )
        return run_workload(inst, mk_group(1200, 4, prompt_len=21, max_new=8))

    done_s, done_i = run(True), run(False)
    key = lambda t: t.traj_id
    assert_same_streams(sorted(done_s, key=key), sorted(done_i, key=key))
    # divergence: not every member produced the same response
    responses = {tuple(t.response) for t in done_s}
    assert len(responses) > 1, "stochastic members never diverged"


def test_group_preemption_and_readmission_matches_unconstrained():
    """A pool too small for the whole group preempts members mid-decode;
    preempted members re-admit via exclusive re-prefill. Greedy streams
    must match a run with an ample pool, and no block may leak."""
    reset_traj_ids()
    NO_EOS = -1

    def run(pool_blocks):
        inst = RolloutInstance(
            0, CFG, PARAMS, 0, max_slots=3, max_len=64,
            temperature=0.0, seed=0, eos_id=NO_EOS,
            paged=True, kv_block_size=8, kv_pool_blocks=pool_blocks,
            share_prefix=True,
        )
        trajs = mk_group(1300, 3, prompt_len=13, max_new=30)
        inst.route_many(trajs)
        done = []
        for _ in range(400):
            done.extend(inst.step())
            inst.allocator.check()
            if len(done) == 3:
                break
        return inst, sorted(done, key=lambda t: t.traj_id)

    inst_small, done_small = run(10)   # 80-token pool for ~43*3 tokens
    inst_big, done_big = run(64)
    assert inst_small.preemptions > 0, "pool never exhausted"
    assert inst_big.preemptions == 0
    assert len(done_small) == len(done_big) == 3
    for a, b in zip(done_small, done_big):
        assert a.traj_id == b.traj_id
        assert a.response == b.response
    assert inst_small.allocator.used_blocks == 0
    inst_small.allocator.check()


@pytest.mark.parametrize("lazy", [True, False])
def test_group_interrupt_releases_shared_blocks_once(lazy):
    """Interrupting members one by one frees only their exclusive blocks;
    the shared prompt blocks return to the pool with the last member.
    Under lazy CoW undiverged members own NO exclusive blocks — the whole
    group footprint (full blocks + one shared tail) releases with the
    last member."""
    reset_traj_ids()
    bs, P, G = 16, 37, 3
    inst = mk_sharing(share=True, slots=G, block_size=bs, lazy_cow=lazy)
    group = mk_group(1400, G, prompt_len=P)
    inst.route_many(group)
    used = inst.allocator.used_blocks
    n_full = P // bs
    assert used == n_full + (1 if lazy else G)
    inst.interrupt([group[0].traj_id])
    # lazy: member 0 never diverged, so it frees nothing (refs drop only);
    # eager: its private tail copy returns to the pool
    assert inst.allocator.used_blocks == used - (0 if lazy else 1)
    inst.interrupt([group[1].traj_id])
    assert inst.allocator.used_blocks == used - (0 if lazy else 2)
    inst.interrupt([group[2].traj_id])
    assert inst.allocator.used_blocks == 0                 # prefix released
    assert inst.snapshot().prefix_groups == {}
    assert inst.snapshot().prefix_tail_members == {}
    inst.allocator.check()


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_group_straggler_forks_resident_prefix_across_waves(temperature):
    """A member admitted AFTER its siblings (no free slot in their wave)
    forks the still-resident prefix: no duplicate prompt blocks, and the
    token stream still matches the all-independent path exactly — greedy
    AND stochastic (suffix prefill reproduces the logits to float32
    rounding, and sampling keys are pure functions of (seed, traj_id,
    position)); behavior logprobs agree within LOGPROB_ATOL."""
    reset_traj_ids()
    bs, P = 16, 37                       # 2 full shared blocks + tail
    NO_EOS = -1

    def run(share):
        inst = RolloutInstance(
            0, CFG, PARAMS, 0, max_slots=2, max_len=64,
            temperature=temperature, seed=0, eos_id=NO_EOS,
            paged=True, kv_block_size=bs, share_prefix=share,
        )
        group = mk_group(1600, 3, prompt_len=P, max_new=6)
        # stagger budgets: member 0 finishes first, freeing a slot while
        # member 1 still holds the shared prefix for the straggler to fork
        group[0].max_new_tokens = 3
        inst.route_many(group)           # only 2 slots: member 3 waits
        assert inst.n_active() == 2
        if share:
            # two members share fully (two full blocks + the one lazy
            # tail); the third joins when a slot frees
            assert inst.allocator.used_blocks == 2 + 1
        done = []
        for _ in range(100):
            done.extend(inst.step())
            inst.allocator.check()
            if len(done) == 3:
                break
        return inst, sorted(done, key=lambda t: t.traj_id)

    inst_s, done_s = run(True)
    inst_i, done_i = run(False)
    assert inst_s.shared_prefix_hits == 2   # one in-wave, one cross-wave fork
    assert_same_streams(done_s, done_i)
    assert inst_s.allocator.used_blocks == 0
    inst_s.allocator.check()


def test_straggler_fork_survives_donor_interrupt_mid_decode():
    """Regression: a straggler forks the resident prefix, then its DONOR is
    interrupted mid-decode. The forked blocks are refcounted, so the
    donor's release must not free them out from under the straggler — the
    allocator invariants hold at every step and the straggler's stream
    still matches the all-independent path (tokens exactly, logprobs
    within LOGPROB_ATOL)."""
    reset_traj_ids()
    bs, P = 16, 37
    NO_EOS = -1

    def run(share):
        inst = RolloutInstance(
            0, CFG, PARAMS, 0, max_slots=2, max_len=64,
            temperature=0.0, seed=0, eos_id=NO_EOS,
            paged=True, kv_block_size=bs, share_prefix=share,
        )
        group = mk_group(1700, 3, prompt_len=P, max_new=8)
        group[0].max_new_tokens = 2      # frees a slot while member 1 decodes
        inst.route_many(group)
        done = []
        for _ in range(100):
            done.extend(inst.step())
            inst.allocator.check()
            if any(t.traj_id == 1700 for t in done):
                break
        # the straggler was admitted in the wave that freed the slot; the
        # donor (member 1) is mid-decode — kick the donor now
        tbl = list(inst.allocator.table(1702))
        kicked = inst.interrupt([1701])
        assert [t.traj_id for t in kicked] == [1701]
        inst.allocator.check()
        if share:
            # the straggler is now the sole owner of the forked prompt
            # blocks: the donor's release decremented, not freed, them
            for blk in tbl[:2]:
                assert inst.allocator.refcount(blk) == 1
        for _ in range(100):
            done.extend(inst.step())
            inst.allocator.check()
            if any(t.traj_id == 1702 for t in done):
                break
        return inst, [t for t in done if t.traj_id == 1702]

    inst_s, done_s = run(True)
    inst_i, done_i = run(False)
    assert inst_s.shared_prefix_hits == 2
    assert len(done_s) == 1
    assert_same_streams(done_s, done_i)
    assert inst_s.allocator.used_blocks == 0
    inst_s.allocator.check()


def test_lazy_cow_skips_copies_for_members_that_never_decode():
    """Copy traffic is strictly lower under lazy CoW: members interrupted
    between admission and their first decode step never diverge, so their
    tail copies never happen — eager CoW has already paid them at
    admission. The surviving member's stream is unchanged (the last
    undiverged owner appends in place)."""
    reset_traj_ids()

    def run(lazy):
        inst = mk_sharing(share=True, slots=4, lazy_cow=lazy)
        group = mk_group(1800, 3, prompt_len=21, max_new=4)
        inst.route_many(group)
        admission_copies = inst.block_copies
        # coordinator kicks two members before the first decode dispatch
        inst.interrupt([1801, 1802])
        inst.allocator.check()
        done = []
        for _ in range(20):
            done.extend(inst.step())
            inst.allocator.check()
            if done:
                break
        return inst, admission_copies, done

    inst_l, adm_l, done_l = run(True)
    inst_e, adm_e, done_e = run(False)
    assert adm_e == 2                    # eager: G-1 tail copies up front
    assert adm_l == 0
    assert inst_e.block_copies == 2
    assert inst_l.block_copies == 0      # survivor was the last owner
    assert inst_l.block_copies < inst_e.block_copies
    assert_same_streams(done_l, done_e)
    assert inst_l.allocator.used_blocks == 0
    inst_l.allocator.check()


def test_group_partial_members_do_not_share():
    """A member with a partial response (diverged KV) must re-prefill
    exclusively even when routed alongside its fresh siblings."""
    reset_traj_ids()
    inst = mk_sharing(share=True, slots=4)
    group = mk_group(1500, 3, prompt_len=21, max_new=12)
    partial = group[0]
    partial.response = [5, 6]
    partial.behavior_logprobs = [-1.0, -1.0]
    inst.route_many(group)
    # siblings 1,2 share; the partial member prefills alone
    assert inst.shared_prefix_hits == 1
    assert inst.prefill_tokens == 23 + 21
    inst.allocator.check()


def test_paged_admission_wave_uses_live_free_count():
    """Blocks drawn by earlier admissions in the same wave must not be
    double-counted against the pool: with 9 free blocks, a 5-block and a
    3-block trajectory admit together."""
    reset_traj_ids()
    inst = RolloutInstance(
        0, CFG, PARAMS, 0, max_slots=4, max_len=64, temperature=0.0,
        paged=True, kv_block_size=8, kv_pool_blocks=9,
    )
    a = mk_traj(970, prompt_len=33, max_new=4)   # ceil(33/8) = 5 blocks
    b = mk_traj(971, prompt_len=17, max_new=4)   # ceil(17/8) = 3 blocks
    inst.route_many([a, b])
    s = inst.snapshot()
    assert s.run_trajs == {970, 971}
    assert inst.allocator.used_blocks == 8
    inst.allocator.check()


# ============================================== per-slot PRNG key streams
# The sampling key for trajectory t's p-th token is
# fold_in(fold_in(base, t), p) — a pure function of (seed, traj_id,
# position). Stochastic token streams are therefore invariant under
# batch composition, slot assignment, instance identity, and migration
# destination; behavior logprobs are equal within LOGPROB_ATOL where the
# batch shape changes.


def test_stochastic_stream_invariant_under_batch_composition():
    """The same trajectory sampled alone vs sharing the batch with three
    neighbours (different compaction bucket, different slot) produces the
    identical stochastic token stream."""
    reset_traj_ids()

    def run(neighbours):
        inst = RolloutInstance(
            0, CFG, PARAMS, 0, max_slots=4, max_len=64, temperature=1.0,
            seed=5,
        )
        target = mk_traj(700, prompt_len=9, max_new=10)
        others = [
            mk_traj(710 + i, prompt_len=6 + i, max_new=10)
            for i in range(neighbours)
        ]
        # neighbours admitted FIRST: the target lands in a different slot
        # and a different compaction bucket than when alone
        run_workload(inst, others + [target])
        return target

    alone = run(0)
    crowded = run(3)
    assert_same_streams([alone], [crowded])


def test_stochastic_stream_invariant_under_instance_identity():
    """Different inst_id, same seed: identical streams — a migrated
    trajectory would sample the same tokens on any replica."""
    reset_traj_ids()

    def run(inst_id):
        inst = RolloutInstance(
            inst_id, CFG, PARAMS, 0, max_slots=2, max_len=64,
            temperature=1.0, seed=9,
        )
        t = mk_traj(800, prompt_len=7, max_new=8)
        run_workload(inst, [t])
        return t

    assert_same_streams([run(0)], [run(5)])


def test_stochastic_migration_destination_invariant():
    """Interrupt mid-stream, then finish on instance B vs instance C (with
    different occupancy): the continuation resumes the key stream at its
    position and the final token streams match (logprobs within
    LOGPROB_ATOL)."""
    reset_traj_ids()

    def run(busy_dest):
        src = RolloutInstance(
            0, CFG, PARAMS, 0, max_slots=4, max_len=64, temperature=1.0,
            seed=13,
        )
        t = mk_traj(900, prompt_len=8, max_new=12)
        src.route(t)
        for _ in range(4):
            src.step()
        src.interrupt([t.traj_id])
        dest = RolloutInstance(
            1 + int(busy_dest), CFG, PARAMS, 0, max_slots=4, max_len=64,
            temperature=1.0, seed=13,
        )
        if busy_dest:
            # different batch composition at the destination
            dest.route_many(
                [mk_traj(910 + i, prompt_len=5 + i, max_new=20)
                 for i in range(2)]
            )
        dest.route(t)
        for _ in range(80):
            if t.finished:
                break
            dest.step()
        assert t.finished
        return t

    assert_same_streams([run(False)], [run(True)])


def test_stream_keys_match_scalar_stream_key():
    from repro.rollout import sampler

    base = jax.random.PRNGKey(3)
    ids = jax.numpy.asarray([4, 99, 4], jax.numpy.uint32)
    pos = jax.numpy.asarray([0, 7, 1], jax.numpy.uint32)
    batched = np.asarray(sampler.stream_keys(base, ids, pos))
    for row, (i, p) in enumerate(zip([4, 99, 4], [0, 7, 1])):
        np.testing.assert_array_equal(
            batched[row], np.asarray(sampler.stream_key(base, i, p))
        )
