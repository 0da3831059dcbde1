"""Distribution layer tests.

Sharding rules are pure functions -> tested directly. Multi-device
semantics (compressed psum, mesh construction, small-scale lower+compile)
run in SUBPROCESSES with XLA_FLAGS=--xla_force_host_platform_device_count
so the main test process keeps its single CPU device (per the assignment:
the 512-device trick is dry-run-only)."""
import subprocess
import sys
import textwrap

from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.distributed.sharding import param_spec


class FakeMesh:
    """Duck-typed mesh: only ``shape`` (axis sizes) is consulted by rules."""

    def __init__(self, **axes):
        self.shape = dict(axes)


MESH = FakeMesh(data=16, model=16)


def test_param_spec_attention_weights():
    # (L, D, H*hd): D -> data, heads -> model
    assert param_spec(MESH, "['blocks']['wq']", (48, 5120, 5120)) == P(None, "data", "model")
    assert param_spec(MESH, "['blocks']['wo']", (48, 5120, 5120)) == P(None, "model", "data")


def test_param_spec_embed_vocab_padding_divisible():
    cfg = get_arch("granite-3-8b")
    assert cfg.vocab_size % 16 != 0        # raw vocab does NOT divide
    assert cfg.padded_vocab % 256 == 0     # padded vocab shards cleanly
    spec = param_spec(MESH, "['embed']", (cfg.padded_vocab, cfg.d_model))
    assert spec == P("model", "data")


def test_param_spec_nondivisible_falls_back_to_replication():
    # head dim 100 does not divide model=16 -> replicated on that dim
    spec = param_spec(MESH, "['blocks']['wq']", (4, 128, 100))
    assert spec == P(None, "data", None)


def test_param_spec_moe_expert_parallel():
    spec = param_spec(MESH, "['blocks']['we_gate']", (40, 16, 6144, 10752))
    assert spec == P(None, "model", "data", None)


def test_param_spec_opt_state_mirrors_params():
    a = param_spec(MESH, "['m']['blocks']['wq']", (48, 5120, 5120))
    b = param_spec(MESH, "['blocks']['wq']", (48, 5120, 5120))
    assert a == b


def test_param_spec_norms_replicated():
    assert param_spec(MESH, "['blocks']['attn_norm']", (48, 5120)) == P()


def _run_subprocess(code: str, devices: int = 8) -> str:
    prog = (
        f"import os; os.environ['XLA_FLAGS']="
        f"'--xla_force_host_platform_device_count={devices}'\n"
        + textwrap.dedent(code)
    )
    import os

    out = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True, text=True, timeout=480,
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


def test_production_mesh_shapes_subprocess():
    out = _run_subprocess(
        """
        import jax
        from repro.launch.mesh import make_production_mesh
        m = make_production_mesh()
        assert m.shape == {"data": 16, "model": 16}, m.shape
        m2 = make_production_mesh(multi_pod=True)
        assert m2.shape == {"pod": 2, "data": 16, "model": 16}
        assert m2.size == 512
        print("MESH_OK")
        """,
        devices=512,
    )
    assert "MESH_OK" in out


def test_compressed_psum_subprocess():
    out = _run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from repro.distributed.collectives import psum_compressed, shard_map
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((4,), ("data",))
        x = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 7.0

        @partial(shard_map, mesh=mesh,
                 in_specs=jax.sharding.PartitionSpec("data"),
                 out_specs=jax.sharding.PartitionSpec("data"))
        def f(xs):
            return psum_compressed(xs, "data")

        got = f(x)
        want = jnp.broadcast_to(x.sum(0, keepdims=True), x.shape)
        err = float(jnp.abs(got - want).max()) / float(jnp.abs(want).max())
        assert err < 0.02, err   # int8 quantization tolerance
        print("PSUM_OK", err)
        """,
        devices=4,
    )
    assert "PSUM_OK" in out


def test_gpipe_matches_sequential_subprocess():
    """4-stage GPipe over a toy MLP stack == sequential application."""
    out = _run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import gpipe_apply
        from repro.launch.mesh import auto_mesh
        S, M, mb, d = 4, 6, 2, 8
        mesh = auto_mesh((S,), ("stage",))
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (S, d, d)) * 0.3
        b = jax.random.normal(jax.random.fold_in(key, 1), (S, d)) * 0.1
        x = jax.random.normal(jax.random.fold_in(key, 2), (M, mb, d))

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        got = gpipe_apply(stage_fn, {"w": w, "b": b}, x, mesh=mesh)
        ref = x
        for s in range(S):
            ref = jnp.tanh(ref @ w[s] + b[s])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        print("GPIPE_OK")
        """,
        devices=4,
    )
    assert "GPIPE_OK" in out


def test_small_mesh_train_step_executes_subprocess():
    """Numerically execute the sharded RL train step on an 8-device mesh
    (reduced arch) — proves in/out shardings are not just lowerable but
    runnable."""
    out = _run_subprocess(
        """
        import jax, jax.numpy as jnp
        from repro.configs import get_arch
        from repro.distributed import sharding as shd
        from repro.training.optimizer import AdamWConfig, init_opt_state
        from repro.training.train_step import make_rl_train_step
        from repro.models import model as M
        from repro.launch.mesh import auto_mesh

        cfg = get_arch("qwen2-1.5b").reduced()
        mesh = auto_mesh((4, 2), ("data", "model"))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        opt = init_opt_state(params)
        b, t = 8, 32
        batch = {
            "tokens": jnp.ones((b, t), jnp.int32) * 5,
            "behavior_logprobs": jnp.full((b, t), -2.0),
            "mask": jnp.ones((b, t)),
            "advantages": jnp.linspace(-1, 1, b),
        }
        p_sh = shd.params_shardings(mesh, params)
        o_sh = shd.opt_shardings(mesh, opt)
        b_sh = shd.train_batch_shardings(mesh, batch)
        step = jax.jit(
            make_rl_train_step(cfg, AdamWConfig(lr=1e-3)),
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, None),
        )
        params = jax.device_put(params, p_sh)
        opt = jax.device_put(opt, o_sh)
        batch = jax.device_put(batch, b_sh)
        p2, o2, m = step(params, opt, batch)
        assert jnp.isfinite(m["loss"]), m
        print("TRAIN_OK", float(m["loss"]))
        """,
        devices=8,
    )
    assert "TRAIN_OK" in out
