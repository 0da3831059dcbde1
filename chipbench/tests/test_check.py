"""A whole run of a test-size cell on the CPU, past the harness's look for
a chip: sound, ``correct`` is true; with the timed path broken underneath
it is false. The control (the reference in bfloat16 throughout) takes the
program's place through the same comparison."""
import json
import time
from pathlib import Path

import pytest

DATA = Path(__file__).resolve().parent / "data"
CELL = {"name": "tiny", "config": "tiny", "traffic": "tiny-rl", "chips": 1}
# the limits of the qwen2-1.5b.rlvr cell: what holds the chip runs holds here
LIMITS = json.loads(
    (Path(__file__).resolve().parents[1] / "limits" / "qwen2-1.5b.rlvr.json")
    .read_text()
)


def run(seed, fault=None, control=False, warm=True, keep=None,
        config="tiny"):
    from chipbench import harness

    return harness.run_cell(
        dict(CELL, config=config), {"per_layer": []}, seed=seed,
        seconds=1.0, trace=False,
        t_start=time.perf_counter(), config_dir=DATA, traffic_dir=DATA,
        limits={k: v["limit"] for k, v in LIMITS.items()}, fault=fault,
        control=control, warm=warm, keep=keep,
    )


def test_sound_run_is_correct():
    res = run(2**32 + 3)
    assert res.correct, res.checks
    assert res.attempted > 0 and res.failed == 0
    assert set(res.metrics) == {"rl_tokens_per_s", "traj_p95_s", "setup_s"}
    assert {c["name"] for c in res.checks} == set(LIMITS) | {
        "staleness", "lifecycle"}


def test_control_readings_stand_in_the_programs_place():
    """With ``control`` the compared numbers are the bfloat16 reference's,
    through the harness's own comparison. At test size on the CPU the
    program meets the reference to round-off and the control does not."""
    from chipbench import check

    keep = {}
    res = run(2**32 + 3, control=True, keep=keep)
    ids = [t["id"] for t in keep["trajs"]]
    ref = keep["reference"]
    control = check.numbers(keep["control"], ref, ref.grad_norms, ids)
    program = check.numbers(keep["program"], ref, ref.grad_norms, ids)
    got = {c["name"]: c["value"] for c in res.checks}
    assert all(got[k] == control[k] for k in LIMITS)
    assert got["lp_gap"] > 100 * program["lp_gap"]


@pytest.mark.parametrize("fault", ["frozen", "half_batch", "token"])
def test_broken_timed_path_is_not_correct(fault):
    res = run(11, fault=fault)
    assert not res.correct, res.checks


@pytest.mark.parametrize("fault", [None, "token"])
def test_untied_head_checked_by_the_files_reference(fault):
    """A configuration with an untied LM head runs end to end through the
    reference model its file names: sound, ``correct`` is true; with a
    token altered where it is produced, false."""
    res = run(2**32 + 5, fault=fault, config="tiny-untied")
    assert res.correct == (fault is None), res.checks


def test_warming_the_shapes_changes_no_compared_number():
    """Calibration skips the shape warm-up and the window: the compared
    numbers come from the first training steps and what they consumed."""
    warm, cold = {}, {}
    a = run(7, warm=True, keep=warm)
    b = run(7, warm=False, keep=cold)
    assert [c["value"] for c in a.checks] == [c["value"] for c in b.checks]
    assert [t["response"] for t in warm["trajs"]] == \
           [t["response"] for t in cold["trajs"]]


def test_budget_alone_ends_a_response():
    """Every consumed response runs to its budget (the program may add one
    token): the harness turns off the engines' EOS, which random weights
    sample now and then, so none ends early."""
    from chipbench import check, harness, traffic as T
    from repro.runtime.async_runtime import AsyncRLRuntime, RuntimeConfig

    seed = 2**32 + 11
    cfg, conf = harness.load_config("tiny", DATA)
    mix = T.load_mix("tiny-rl", DATA)
    t = T.Traffic(mix, seed, cfg.vocab_size)
    rt = AsyncRLRuntime(cfg, RuntimeConfig(
        batch_size=mix.batch_size, group_size=mix.group_size,
        n_instances=mix.n_instances, max_slots=mix.max_slots,
        max_len=mix.max_len, paged_kv=True, kv_block_size=mix.kv_block_size,
        scheduler="tick",
        seed=check.weights_seed(seed)))
    harness.install_traffic(rt, t, harness.Ledger())
    assert all(h.inner.eos_id < 0 for h in rt.instances.values())
    del rt
    keep = {}
    res = run(seed, keep=keep)
    assert res.correct, res.checks
    for traj in keep["trajs"]:
        budgets = {t.budget(traj["group"], m) for m in range(mix.group_size)}
        n = len(traj["response"])
        assert n in budgets or n - 1 in budgets, (n, sorted(budgets))
