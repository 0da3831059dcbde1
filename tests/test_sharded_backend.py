"""ShardedBackend: one rollout instance spanning a multi-device mesh.

The contract under test (repro.rollout.sharded):

* decode matches the single-device paged engine — tokens exactly,
  behavior logprobs within ``LOGPROB_ATOL`` — across batched admission,
  CoW prefix sharing, cross-wave prefix forks, and pool-exhaustion
  preemption;
* the paged K/V pool stays head-sharded through prefill scatters, CoW
  copies, and decode steps (per-device bytes = total / shard_count);
* engine, SimBackend, and CostModel report identical per-device kv_cache
  for the same routed group at shard_count > 1;
* the end-to-end runtime runs on sharded instances (RuntimeConfig.
  rollout_shards).

Multi-device paths run in subprocesses with forced host device counts
(the tests/test_distributed.py pattern) so the main pytest process keeps
its single CPU device. Validation-only tests run in-process.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.snapshot import InstanceSnapshot
from repro.distributed.sharding import validate_rollout_shards
from repro.rollout.backend import BACKENDS

NO_EOS = -1
# Behavior logprobs of the sharded and single-device engines agree to
# ~2e-6, not bitwise: the partitioned program batches the same float32
# sums differently and XLA:CPU reassociates them by shape. Tokens stay
# exactly equal; a wrong head shard or block table moves logprobs by O(1).
LOGPROB_ATOL = 1e-5


def _cfg(n_heads=4, n_kv_heads=2):
    from repro.configs import get_arch

    return dataclasses.replace(
        get_arch("qwen2-1.5b").reduced(),
        n_heads=n_heads,
        n_kv_heads=n_kv_heads,
        head_dim=16,
        d_model=n_heads * 16,
    )


def _mk_trajs():
    """Workload mixing every admission path: two 3-member groups (one
    shared prefill + CoW tails each), a straggler that forks a resident
    prefix cross-wave, and plain singles."""
    from repro.core.types import Trajectory

    out = []
    rng = np.random.RandomState(0)
    tid = 0
    prompts = {}
    for gid in range(2):
        prompts[gid] = list(rng.randint(3, 200, 13 + 5 * gid))
        for _ in range(3):
            out.append(
                Trajectory(
                    traj_id=tid,
                    prompt=list(prompts[gid]),
                    group_id=gid,
                    max_new_tokens=18,
                )
            )
            tid += 1
    # straggler: same group/prompt as group 0, routed a later wave
    out.append(
        Trajectory(
            traj_id=tid,
            prompt=list(prompts[0]),
            group_id=0,
            max_new_tokens=18,
        )
    )
    tid += 1
    for i in range(3):
        out.append(
            Trajectory(
                traj_id=tid,
                prompt=list(rng.randint(3, 200, 7 + i)),
                max_new_tokens=18,
            )
        )
        tid += 1
    return out


def run_scenario(shard_count, temperature=0.0, kv_pool_blocks=14, n_kv_heads=2):
    """Drive one engine over the mixed workload; the tight pool forces
    preemption mid-decode. Returns (per-traj results, telemetry)."""
    import jax

    from repro.models import model as M
    from repro.rollout.backend import create_backend

    cfg = _cfg(n_kv_heads=n_kv_heads)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(
        cfg=cfg,
        params=params,
        version=0,
        max_slots=4,
        max_len=64,
        temperature=temperature,
        eos_id=NO_EOS,
        seed=3,
        paged=True,
        kv_block_size=8,
        kv_pool_blocks=kv_pool_blocks,
        share_prefix=True,
    )
    if shard_count > 1:
        inst = create_backend("sharded", 0, shard_count=shard_count, **kw)
    else:
        inst = create_backend("jax", 0, **kw)
    ts = _mk_trajs()
    inst.route_many(ts[:6])
    done = []
    for step in range(200):
        done.extend(inst.step())
        inst.allocator.check()
        if step == 3:
            inst.route_many(ts[6:])
        if len(done) == len(ts):
            break
    out = {t.traj_id: (list(t.response), list(t.behavior_logprobs)) for t in done}
    telemetry = {
        "preemptions": inst.preemptions,
        "shared_prefix_hits": inst.shared_prefix_hits,
        "prefill_tokens_saved": inst.prefill_tokens_saved,
        "kv_bytes": inst.kv_bytes(),
    }
    return out, telemetry, inst


def run_runtime_smoke(shards):
    """One training step of the full async runtime on sharded instances."""
    from repro.runtime.async_runtime import AsyncRLRuntime, RuntimeConfig

    cfg = _cfg(n_heads=2, n_kv_heads=2)
    rcfg = RuntimeConfig(
        batch_size=2,
        group_size=2,
        n_instances=2,
        max_slots=4,
        max_len=64,
        max_new_tokens=6,
        total_steps=1,
        paged_kv=True,
        kv_block_size=8,
        rollout_shards=shards,
    )
    rt = AsyncRLRuntime(cfg, rcfg)
    history = rt.run(max_ticks=200)
    return len(history)


# ------------------------------------------------------------- in-process
def test_validate_rollout_shards_rejects_nondivisible_heads():
    validate_rollout_shards(2, n_heads=4, n_kv_heads=2)
    with pytest.raises(ValueError, match="divide"):
        validate_rollout_shards(3, n_heads=4, n_kv_heads=2)
    with pytest.raises(ValueError, match="divide"):
        validate_rollout_shards(4, n_heads=4, n_kv_heads=2)
    with pytest.raises(ValueError, match=">= 1"):
        validate_rollout_shards(0, n_heads=4, n_kv_heads=2)


def test_sharded_backend_registered():
    assert "sharded" in BACKENDS


def test_sharded_backend_requires_paged():
    from repro.rollout.sharded import ShardedBackend

    with pytest.raises(ValueError, match="paged"):
        ShardedBackend(0, _cfg(), None, 0, shard_count=2, paged=False)


def test_make_rollout_mesh_insufficient_devices_message():
    from repro.launch.mesh import make_rollout_mesh

    with pytest.raises(ValueError, match="device_count"):
        make_rollout_mesh(99999)
    with pytest.raises(ValueError, match=">= 1"):
        make_rollout_mesh(0)


def test_sim_backend_reports_per_device_bytes():
    """SimBackend at shard_count=S reports exactly 1/S of the unsharded
    per-instance bytes — the pool spreads over head shards."""
    from repro.core import PAPER_H20_QWEN3_30B
    from repro.core.types import Trajectory
    from repro.rollout.backend import SimBackend

    cm1 = dataclasses.replace(
        PAPER_H20_QWEN3_30B, block_size=16, kv_budget=float("inf")
    )
    cm4 = dataclasses.replace(cm1, shard_count=4)

    def route(cm):
        sim = SimBackend(0, cm)
        for tid, plen in ((10, 6), (11, 20)):
            t = Trajectory(
                traj_id=tid,
                prompt=list(np.random.RandomState(tid).randint(3, 17, plen)),
                max_new_tokens=8,
            )
            t.sim_target_len = 8
            sim.route(t, 0.0)
        return sim.snapshot()

    s1, s4 = route(cm1), route(cm4)
    assert s4.kv_cache == s1.kv_cache / 4
    assert s4.shard_count == 4 and s1.shard_count == 1


def test_snapshot_discard_scales_by_shard_count():
    """discard() releases per-device bytes: k5 is the pod-total per-token
    footprint, the snapshot basis is one device."""
    k5 = 128.0
    s = InstanceSnapshot(
        inst_id=0,
        kv_cache=k5 * 32 / 4,
        run_trajs={1},
        traj_lengths={1: 32},
        shard_count=4,
    )
    s.discard([1], bytes_per_token=k5, block_size=16)
    assert s.kv_cache == 0.0


# ------------------------------------------------------------ subprocess
def _run_subprocess(code: str, devices: int = 8) -> str:
    prog = (
        f"import os; os.environ['XLA_FLAGS']="
        f"'--xla_force_host_platform_device_count={devices}'\n"
        + textwrap.dedent(code)
    )
    out = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True,
        text=True,
        timeout=480,
        env={
            "PYTHONPATH": "src",
            "PATH": "/usr/bin:/bin",
            # without this the child jax probes for a TPU backend (libtpu
            # ships in the image) and stalls minutes on metadata retries.
            # On a machine with a TPU it also keeps the child off the chip,
            # which belongs to one process at a time.
            "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
        },
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_greedy_bitwise_equivalence_subprocess():
    """The acceptance bit: greedy decode on a 4-device ShardedBackend
    matches the single-device paged engine (tokens exactly, behavior
    logprobs within LOGPROB_ATOL) across admission, CoW prefix sharing,
    and preemption."""
    out = _run_subprocess(
        """
        import numpy as np
        from tests.test_sharded_backend import LOGPROB_ATOL, run_scenario

        ref, tel_ref, _ = run_scenario(1, n_kv_heads=4)
        shd, tel_shd, inst = run_scenario(4, n_kv_heads=4)
        assert set(ref) == set(shd), (sorted(ref), sorted(shd))
        for tid in sorted(ref):
            assert ref[tid][0] == shd[tid][0], (tid, "tokens diverged")
            np.testing.assert_allclose(
                shd[tid][1], ref[tid][1], rtol=0, atol=LOGPROB_ATOL,
                err_msg=f"traj {tid} logprobs diverged",
            )
        # the tight pool preempted on both engines, identically
        assert tel_ref["preemptions"] > 0
        assert tel_ref["preemptions"] == tel_shd["preemptions"]
        assert tel_ref["shared_prefix_hits"] == tel_shd["shared_prefix_hits"]
        assert (
            tel_ref["prefill_tokens_saved"] == tel_shd["prefill_tokens_saved"]
        )
        # group 0 shares at admission (+2); later members fork resident
        # prefixes cross-wave — slot pressure decides how many
        assert tel_ref["shared_prefix_hits"] >= 3
        # per-device accounting: the sharded pool reports 1/4 the bytes
        assert tel_shd["kv_bytes"] == tel_ref["kv_bytes"] / 4
        # the pool stayed head-sharded end to end
        spec = inst.cache["k"].sharding.spec
        assert spec[3] == "tensor", spec
        shard_shapes = set(inst.shard_sizes())
        full = inst.cache["k"].shape
        assert shard_shapes == {full[:3] + (full[3] // 4,) + full[4:]}
        print("BITWISE_OK")
        """,
        devices=8,
    )
    assert "BITWISE_OK" in out


def test_sharded_stochastic_bitwise_equivalence_subprocess():
    """Same-occupancy stochastic decode also matches: the gathers
    reconstruct the logits to float32 rounding and sampling consumes
    identical keys, so the token streams are equal and the behavior
    logprobs agree within LOGPROB_ATOL."""
    out = _run_subprocess(
        """
        import numpy as np
        from tests.test_sharded_backend import LOGPROB_ATOL, run_scenario

        ref, _, _ = run_scenario(1, temperature=0.7)
        shd, _, _ = run_scenario(2, temperature=0.7)
        assert set(ref) == set(shd)
        for tid in sorted(ref):
            assert ref[tid][0] == shd[tid][0], tid
            np.testing.assert_allclose(
                shd[tid][1], ref[tid][1], rtol=0, atol=LOGPROB_ATOL,
                err_msg=f"traj {tid}",
            )
        print("STOCH_OK")
        """,
        devices=8,
    )
    assert "STOCH_OK" in out


def test_sharded_engine_sim_costmodel_kv_parity_subprocess():
    """Engine / SimBackend / CostModel agree on per-device kv_cache for
    the same routed group at shard_count=2 (the coordinator's one memory
    picture, now per device)."""
    out = _run_subprocess(
        """
        import dataclasses

        import jax
        import numpy as np

        from repro.core import PAPER_H20_QWEN3_30B
        from repro.core.types import Trajectory
        from repro.models import model as M
        from repro.rollout.backend import SimBackend, create_backend
        from tests.test_sharded_backend import _cfg

        cfg = _cfg()
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        bs, plen, g, shards = 8, 19, 3, 2
        k5 = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.hd * 4
        cm = dataclasses.replace(
            PAPER_H20_QWEN3_30B, k5=float(k5), block_size=bs,
            kv_budget=float("inf"), shard_count=shards,
        )
        sim = SimBackend(0, cm, share_prefix=True)
        eng = create_backend(
            "sharded", 1, cfg=cfg, params=params, version=0,
            shard_count=shards, max_slots=4, max_len=64, temperature=0.0,
            paged=True, kv_block_size=bs, share_prefix=True,
        )
        prompt = list(np.random.RandomState(7).randint(3, 17, plen))

        def group(base):
            return [
                Trajectory(
                    traj_id=base + i, prompt=list(prompt), group_id=0,
                    max_new_tokens=50,
                )
                for i in range(g)
            ]

        sim.route_many(group(80), 0.0)
        eng.route_many(group(80), 0.0)
        n_full = plen // bs
        # lazy CoW (the default): shared prompt blocks once, plus ONE
        # shared tail block — nobody has decoded yet, so nobody owns a
        # private copy. Per-device bytes at shard_count=2.
        expected = k5 * bs * (n_full + 1) / shards
        assert sim.snapshot().kv_cache == expected
        assert eng.snapshot().kv_cache == expected
        assert cm.group_kv_bytes_for(
            plen, [plen + 1] * g, undiverged=g
        ) == expected
        # the default (eager/worst-case) view admission decisions use
        assert cm.group_kv_bytes_for(plen, [plen + 1] * g) == (
            k5 * bs * (n_full + g) / shards
        )
        assert sim.snapshot().shard_count == shards
        assert eng.snapshot().shard_count == shards
        # per-member interrupts release per-device exclusive bytes,
        # identically on both, down to zero with the last co-owner
        sim.interrupt([80], 1.0)
        eng.interrupt([80], 1.0)
        assert sim.snapshot().kv_cache == eng.snapshot().kv_cache
        sim.interrupt([81, 82], 1.0)
        eng.interrupt([81, 82], 1.0)
        assert sim.snapshot().kv_cache == 0
        assert eng.snapshot().kv_cache == 0
        print("PARITY_OK")
        """,
        devices=8,
    )
    assert "PARITY_OK" in out


@pytest.mark.slow
def test_runtime_on_sharded_instances_subprocess():
    """RuntimeConfig.rollout_shards wires the sharded backend through the
    full async runtime: coordinator cycles, pulls (params re-sharded onto
    the mesh), rewards, and a training step all execute."""
    out = _run_subprocess(
        """
        from tests.test_sharded_backend import run_runtime_smoke

        assert run_runtime_smoke(2) >= 1
        print("RUNTIME_OK")
        """,
        devices=8,
    )
    assert "RUNTIME_OK" in out
