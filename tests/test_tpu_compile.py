"""Compile the main path's kernels and the trainer step for a TPU v5e chip.

The chip is described, not attached: the TPU compiler that ships with JAX
compiles for it here and refuses what the chip would refuse (unaligned
blocks, scalar stores to VMEM, too much VMEM, a program that does not fit
the device). Nothing runs, so these tests say nothing about results or
speed; the interpret-mode tests in ``test_kernels.py`` check results.

Widths are qwen2-1.5b's as published (12 query heads, 2 KV heads, head dim
128, d_model 1536, vocab 151,936) with 16-token KV blocks: the shapes
``chip_smoke.py`` runs on the chip.

Only one process at a time may load the TPU library, and it keeps it until
it exits. So the topology is described inside a module-scoped fixture,
never at import, and every compile runs in this test process.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch

H, HKV, HD, BS = 12, 2, 128, 16      # qwen2-1.5b heads, head dim; KV block
B, NB, N_BLOCKS = 8, 4, 65           # decode rows, table width, pool blocks


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the compiler logs under the system temp dir unless told otherwise;
    # a described-chip compile written to the persistent cache cannot be
    # read back without a chip, so the cache is off for this module
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    if prev_log is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = prev_log


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in specs]


def _kernel_case(name, dtype):
    """(function, argument shapes) of one Pallas kernel call."""
    from repro.kernels.block_copy import copy_pool_blocks
    from repro.kernels.dapo_loss import dapo_loss
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.paged_attention import (
        paged_decode_attention,
        paged_decode_attention_update,
        paged_prefill_attention,
    )

    i32 = jnp.int32
    pool = ((N_BLOCKS, BS, HKV, HD), dtype)
    if name == "paged_decode_attention":
        return paged_decode_attention, [
            ((B, H, HD), dtype), pool, pool, ((B, NB), i32), ((B,), i32),
        ]
    if name == "paged_decode_attention_update":
        return paged_decode_attention_update, [
            ((B, H, HD), dtype), pool, pool, ((B, HKV, HD), dtype),
            ((B, HKV, HD), dtype), ((B, NB), i32), ((B,), i32),
        ]
    if name == "paged_prefill_attention":
        return paged_prefill_attention, [
            ((2, 16, H, HD), dtype), pool, pool, ((2, NB), i32),
            ((2,), i32), ((2,), i32),
        ]
    if name == "flash_attention":
        return flash_attention, [
            ((B, 32, H, HD), dtype), ((B, 32, HKV, HD), dtype),
            ((B, 32, HKV, HD), dtype),
        ]
    if name == "copy_pool_blocks":
        layered = ((4, N_BLOCKS, BS, HKV, HD), dtype)
        return copy_pool_blocks, [layered, layered, ((4,), i32), ((4,), i32)]
    if name == "dapo_loss":
        # the trainer's (batch, length - 1) token-logprob planes
        return dapo_loss, [
            ((B, 31), dtype), ((B, 31), dtype), ((B,), dtype), ((B, 31), dtype),
        ]
    raise KeyError(name)


KERNELS = (
    "paged_decode_attention",
    "paged_decode_attention_update",
    "paged_prefill_attention",
    "flash_attention",
    "copy_pool_blocks",
    "dapo_loss",
)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name, dtype):
    fn, specs = _kernel_case(name, dtype)
    compiled = jax.jit(fn).lower(*_shapes(one_chip, *specs)).compile()
    # the Pallas kernel itself is in the program, not an XLA fallback
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_lowers_under_its_name(one_chip, name):
    """Each Pallas call passes ``name=``: the device trace names the
    kernel's operation so, and the benchmark's readers match it there."""
    fn, specs = _kernel_case(name, jnp.float32)
    text = jax.jit(fn).lower(*_shapes(one_chip, *specs)).as_text()
    assert f'kernel_name = "{name}"' in text


def test_trainer_step_compiles_for_v5e(one_chip):
    """The trainer's differentiable path: one published-width layer of
    qwen2-1.5b, value_and_grad + AdamW, built with ``TRAINER_IMPL``."""
    from repro.models import model as M
    from repro.training.optimizer import AdamWConfig, init_opt_state
    from repro.training.train_step import TRAINER_IMPL, make_rl_train_step

    cfg = dataclasses.replace(get_arch("qwen2-1.5b"), n_layers=1)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    params = placed(
        jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.PRNGKey(0))
    )
    opt = placed(jax.eval_shape(init_opt_state, params))
    t = 32
    batch = dict(zip(
        ("tokens", "behavior_logprobs", "mask", "advantages"),
        _shapes(one_chip, ((B, t), jnp.int32), ((B, t), jnp.float32),
                ((B, t), jnp.float32), ((B,), jnp.float32)),
    ))
    step = make_rl_train_step(cfg, AdamWConfig(), impl=TRAINER_IMPL)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, opt, batch
    ).compile()
    mem = compiled.memory_analysis()
    # the donated optimizer state is updated in place
    assert mem.alias_size_in_bytes >= 2 * 4 * cfg.n_params * 0.99
