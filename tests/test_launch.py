"""Entry-point plumbing: the compile-cache helper, the serve launcher's
wall limit, and ``chip_smoke.py`` (its refusals, and both of its phases
at a tiny size on the CPU).

Subprocesses get ``JAX_PLATFORMS`` forwarded: without it a child JAX
probes for a TPU backend, and on a machine with a TPU it would contend
for the chip, which belongs to one process at a time.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

from repro.configs import get_arch
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _run(args, *, cwd=ROOT, extra_env=None, timeout=300):
    env = {
        "PYTHONPATH": str(ROOT / "src"),
        "PATH": "/usr/bin:/bin",
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
    }
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=cwd,
    )


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ compile cache
def test_compile_cache_follows_the_environment_variable(tmp_path):
    where = str(tmp_path / "cache")
    out = _run(
        ["-c", textwrap.dedent("""
            import jax
            from repro.launch.compile_cache import use_compile_cache
            print(use_compile_cache())
            print(jax.config.jax_compilation_cache_dir)
        """)],
        extra_env={"JAX_COMPILATION_CACHE_DIR": where},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [where, where]


def test_compile_cache_defaults_to_the_repo_directory(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        where = compile_cache.use_compile_cache()
        assert where == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == where
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# -------------------------------------------------------------------- serve
def test_serve_exits_nonzero_when_the_wall_limit_leaves_requests(tmp_path):
    out = _run(
        ["-m", "repro.launch.serve", "--requests", "2", "--wall-limit", "0"],
        extra_env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")},
    )
    assert out.returncode == 1
    assert "requests unserved" in out.stderr + out.stdout


# --------------------------------------------------------------- chip_smoke
def test_chip_smoke_refuses_to_run_without_a_tpu():
    out = _run(["chip_smoke.py"], extra_env={"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_refuses_to_run_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    out = _run(["chip_smoke.py"], cwd=tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_chip_smoke_loop_phase_on_cpu(capsys):
    """The one-chip phase's checks, on a tiny model on the CPU."""
    cs = _load_chip_smoke()
    size = dict(cs.LOOP)
    size.pop("arch"), size.pop("n_layers")
    cs.run_loop(get_arch("qwen2-1.5b").reduced(), seed=0, **size)
    out = capsys.readouterr().out
    assert "FAILED" not in out
    assert out.count(": ok") == 6


def test_chip_smoke_sharded_phase_on_cpu_subprocess():
    """The four-chip phase on four virtual CPU devices, tiny widths."""
    out = _run(
        ["-c", textwrap.dedent("""
            import dataclasses, importlib.util
            spec = importlib.util.spec_from_file_location(
                "chip_smoke", "chip_smoke.py")
            cs = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(cs)
            from repro.configs import get_arch
            cfg = dataclasses.replace(
                get_arch("granite-3-8b").reduced(), n_heads=4, n_kv_heads=4)
            size = dict(cs.SHARDED)
            size.pop("arch"), size.pop("n_layers")
            cs.run_sharded(cfg, seed=0, **size)
        """)],
        extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FAILED" not in out.stdout
    assert out.stdout.count(": ok") == 3

