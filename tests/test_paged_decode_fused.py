"""The paged decode step as one program.

``PagedDecodeRunner.run`` runs the whole step (row gather, decode, key
padding, sampling, the live-row slices, the ``last_tokens`` and per-slot
scatters) as one ``rollout_decode`` program fed by one packed index buffer,
and reads its outputs back in one transfer. Here it is held bit for bit to
the composition it replaced: a decode program, a separate sampler program
on padded keys, and eager slices and scatter, built from the same model
and sampler functions.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.types import Trajectory, reset_traj_ids
from repro.models import model as M
from repro.rollout.engine import RolloutInstance
from repro.rollout.runners import (
    BATCH_AXIS,
    PagedDecodeRunner,
    gather_rows,
    pad_keys,
    scatter_rows,
)
from repro.rollout.sampler import sample_rows, stream_keys

CFG = get_arch("qwen2-1.5b").reduced()
PARAMS = M.init_params(CFG, jax.random.PRNGKey(0))
SLOTS, MAX_LEN, BS = 6, 40, 8
NB = -(-MAX_LEN // BS)
TEMPERATURE = 1.0


def _old_step(bucket, n):
    """The pre-fusion decode program: gather, decode, per-slot scatter."""

    def step(params, last_tokens, cache, rows, live, tables):
        small = {nm: v for nm, v in cache.items() if nm not in ("k", "v")}
        view = gather_rows(small, rows)
        view["k"], view["v"] = cache["k"], cache["v"]
        logits, new = M.paged_decode_step(
            CFG, params, last_tokens[rows], view, tables
        )
        live_rows = {
            nm: jax.tree_util.tree_map(
                lambda f: jax.lax.slice_in_dim(f, 0, n, axis=BATCH_AXIS[nm]),
                new[nm],
            )
            for nm in small
        }
        out = scatter_rows(small, live_rows, live)
        out["k"], out["v"] = new["k"], new["v"]
        return logits, out, new["pos"][:n]

    return jax.jit(step)


_old_sample = jax.jit(
    lambda lg, ks: sample_rows(lg, ks, temperature=TEMPERATURE)
)


def old_run(cache, active, block_tables, last_tokens, keys, runner):
    """The composition ``PagedDecodeRunner.run`` replaced, call for call."""
    n = len(active)
    bucket = runner.bucket_of(n)
    rows = active + [active[0]] * (bucket - n)
    tables = np.full((bucket, NB), 0, np.int32)
    for r, slot in enumerate(active):
        bt = block_tables[slot]
        tables[r, : len(bt)] = bt
    live = jnp.asarray(active, jnp.int32)
    logits, cache, pos_live = _old_step(bucket, n)(
        PARAMS, last_tokens, cache,
        jnp.asarray(rows, jnp.int32), live, jnp.asarray(tables),
    )
    tokens, blps = _old_sample(logits, pad_keys(keys, bucket))
    tokens, blps = tokens[:n], blps[:n]
    last_tokens = last_tokens.at[live].set(tokens)
    return cache, last_tokens, (
        np.asarray(tokens), np.asarray(blps), np.asarray(pos_live)
    )


def _state(seed):
    """A pool of random K/V, per-slot positions and disjoint block tables
    that cover each slot's next write."""
    rng = np.random.default_rng(seed)
    cache = M.init_paged_cache(CFG, SLOTS, MAX_LEN, SLOTS * NB + 1, BS)
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    cache["k"] = jax.random.normal(kk, cache["k"].shape, cache["k"].dtype)
    cache["v"] = jax.random.normal(kv, cache["v"].shape, cache["v"].dtype)
    pos = rng.integers(1, MAX_LEN - 4, SLOTS)
    cache["pos"] = jnp.asarray(pos, jnp.int32)
    tables = {
        s: list(range(1 + s * NB, 1 + s * NB + int(pos[s]) // BS + 1))
        for s in range(SLOTS)
    }
    last = jnp.asarray(rng.integers(0, CFG.vocab_size, SLOTS), jnp.int32)
    return cache, tables, last


def _keys(active, step):
    return stream_keys(
        jax.random.PRNGKey(7),
        np.asarray([100 + s for s in active], np.uint32),
        np.asarray([step] * len(active), np.uint32),
    )


def _same(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("active", [[3], [4, 0, 2], [5, 1, 2, 0],
                                    list(range(SLOTS))])
@pytest.mark.parametrize("empty_tables", [False, True])
def test_fused_step_matches_the_old_composition(active, empty_tables):
    runner = PagedDecodeRunner(
        CFG, max_slots=SLOTS, blocks_per_seq=NB, temperature=TEMPERATURE
    )
    cache, tables, last = _state(len(active))
    if empty_tables:
        # what the benchmark's decode warm-up passes
        tables = {s: [] for s in active}
    new_cache, new_last = cache, last
    old_cache, old_last = cache, last
    # consecutive steps, so each one reads the tokens and pool the
    # previous one wrote
    for step in range(3):
        keys = _keys(active, step)
        new_cache, new_last, res = runner.run(
            PARAMS, new_cache, active, tables, new_last, keys
        )
        old_cache, old_last, (tok, blp, pos) = old_run(
            old_cache, active, tables, old_last, keys, runner
        )
        assert res.slots == active
        assert res.tokens.dtype == tok.dtype == np.int32
        assert res.logprobs.dtype == blp.dtype == np.float32
        np.testing.assert_array_equal(res.tokens, tok)
        np.testing.assert_array_equal(res.positions, pos)
        np.testing.assert_array_equal(
            res.logprobs.view(np.int32), blp.view(np.int32)
        )
        _same(new_last, old_last)
        _same(new_cache, old_cache)


def test_one_program_per_paged_decode_step():
    """The first paged decode step at a shape no other test uses lowers
    the decode program alone: no sampler program, no eager slice,
    concatenation or scatter (eager ops lower as programs of their own)."""
    from jax._src import dispatch

    reset_traj_ids()
    inst = RolloutInstance(
        0, CFG, PARAMS, 0, max_slots=7, max_len=44, temperature=1.0,
        eos_id=-1, seed=3, paged=True, kv_block_size=4,
    )
    for i in range(3):
        inst.route(Trajectory(
            traj_id=i, prompt=[5 + i, 6, 7, 8, 9], max_new_tokens=20
        ))
    # three live slots in a bucket of four: the key padding and the
    # live-row slices are needed
    assert inst.n_active() == 3
    lowered = []

    def on_duration(event, duration, **kw):
        if event == dispatch.JAXPR_TO_MLIR_MODULE_EVENT:
            lowered.append(str(kw.get("fun_name")))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        inst.step()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert "jit(rollout_decode)" in lowered
    assert set(lowered) <= {"jit(rollout_decode)", "jit(stream_keys)"}
    assert lowered.count("jit(rollout_decode)") == 1
    assert inst.decode_steps == 1
    inst.step()
    assert inst.decode_device_calls / inst.decode_steps == 4
