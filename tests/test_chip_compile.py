"""Ahead-of-time compiles for a described TPU v5e chip (no chip needed).

The TPU compiler is installed with jaxlib's TPU plugin and compiles for
a chip that is described, not attached.  These tests catch what the
interpret-mode suites cannot: block shapes Mosaic refuses, primitives
it cannot lower, scoped-VMEM overruns — at the main path's real widths.

The topology is described inside a module-scoped fixture, never at
import time: only one process at a time may load the TPU library, and
every test worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.schedtwin import DRAS_SWEEP_POOL, ReplayGridConfig
from repro.core import engine as engine_mod
from repro.core.engine import DrainEngine, replay_inputs
from repro.core.objective import resolve_goal
from repro.kernels import policy_eval as pe

J = 256   # the paper trace's slot capacity (150 jobs -> next power of 2)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiled(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [32, 2048])
def test_policy_eval_pass_batched_compiles(k, one_chip, no_cache):
    i32, f32 = jnp.int32, jnp.float32
    rows = [_spec((k, J), dt, one_chip) for dt in (i32, i32) + (f32,) * 4]
    forks = [_spec((k,), f32, one_chip)] * 2
    fn = jax.jit(lambda *a: pe.policy_eval_pass_batched(
        *a, interpret=False))
    _assert_kernel_compiled(
        fn.lower(*rows, *forks, _spec((), i32, one_chip)))


def test_policy_eval_pass_compiles(one_chip, no_cache):
    i32, f32 = jnp.int32, jnp.float32
    args = ([_spec((3, J), i32, one_chip), _spec((J,), i32, one_chip)]
            + [_spec((J,), f32, one_chip)] * 4
            + [_spec((), f32, one_chip)] * 2)
    fn = jax.jit(lambda *a: pe.policy_eval_pass(*a, interpret=False))
    _assert_kernel_compiled(fn.lower(*args))


def test_replay_grid_compiles_with_kernel(one_chip, no_cache):
    """The 2,048-fork paper grid ``chip_smoke.py`` replays: 64
    paper-trace scenarios x the 32-fork sweep pool at J=256."""
    cfg = ReplayGridConfig(scenarios=64, trace="paper",
                           pool=DRAS_SWEEP_POOL)
    pool = cfg.make_pool().spec
    scen = cfg.make_scenarios()
    assert scen.capacity == J
    abstract = jax.tree.map(
        lambda x: _spec(x.shape, x.dtype, one_chip),
        replay_inputs(scen, pool))
    eng = DrainEngine("pallas", interpret=False)
    plan = eng.plan(pool)
    lowered = engine_mod._replay.lower(
        eng, *abstract, plan * cfg.scenarios if plan else None,
        resolve_goal(None), len(cfg.make_pool()))
    _assert_kernel_compiled(lowered)
