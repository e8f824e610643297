"""Compile-only rehearsal for TPU v5e: the chip's compiler, no chip.

The Pallas front-tier kernels and the default device engine are
compiled for a described (not attached) ``v5e:2x2`` topology.  This is
what interpret mode cannot show: Mosaic refuses unaligned slices,
scalar stores to VMEM and 1-D concatenates that the interpreter
accepts.  Kernels are compiled with ``interpret=False`` explicitly —
the backend probe would otherwise see the CPU and take the interpret
branch, and the compile would "pass" with no kernel in it.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.events import ARG_WIDTH
from repro.core.queue import _merge_runs_into_main, tiered3_queue_init
from repro.kernels.queue_front import front_merge, window_extract


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Compiles for a described device are written to the persistent
    # cache but cannot be read back without one: keep the cache off.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (front_cap, max_batch_len): the engine default, and a wider window.
WIDTHS = [(256, 16), (512, 64)]


@pytest.mark.parametrize("F,k", WIDTHS)
def test_window_extract_compiles_for_v5e(one_chip, F, k):
    s = lambda shape, dtype: _spec(one_chip, shape, dtype)  # noqa: E731
    compiled = jax.jit(
        lambda *a: window_extract(*a, k=k, interpret=False)
    ).lower(
        s((F,), jnp.float32), s((F,), jnp.int32),
        s((F, ARG_WIDTH), jnp.float32), s((F,), jnp.int32),
        s((3,), jnp.float32), s((), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("F,R", WIDTHS)
def test_front_merge_compiles_for_v5e(one_chip, F, R):
    s = lambda shape, dtype: _spec(one_chip, shape, dtype)  # noqa: E731
    compiled = jax.jit(
        lambda *a: front_merge(*a, interpret=False)
    ).lower(
        s((F,), jnp.float32), s((F,), jnp.int32),
        s((F, ARG_WIDTH), jnp.float32), s((F,), jnp.int32),
        s((), jnp.int32),
        s((R,), jnp.float32), s((R,), jnp.int32),
        s((R, ARG_WIDTH), jnp.float32), s((R,), jnp.int32),
        s((R,), jnp.bool_),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_default_engine_compiles_for_v5e(one_chip):
    """PHOLD's whole super-step loop on the default path (tiered3, XLA
    queue ops, switch dispatch) at capacity 4096."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "examples"))
    import phold

    num_lps = 256
    sim = phold.build_program(
        num_lps=num_lps, t_stop=float("inf"), max_batch_len=16,
        capacity=4096, msgs_per_lp=16, seed=0,
    ).build(backend="device")

    def spec(x):
        return _spec(one_chip, x.shape, x.dtype)

    state = jax.tree.map(
        spec, jax.eval_shape(lambda: phold.initial_state(num_lps)))
    queue = jax.tree.map(
        spec, jax.eval_shape(lambda: sim.engine.initial_queue(())))
    compiled = sim.engine.lower_run(state, queue).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_pool_merge_compiles_without_ring_sort_for_v5e(one_chip):
    """The run pool's merge into the main ring at PHOLD's geometry
    (capacity 2^20 + 2^16, 8 runs of 256): the only sort left in the
    optimised HLO is the pool's own, far shorter than the ring."""
    queue = jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda: tiered3_queue_init(
            1_114_112, front_cap=256, stage_cap=256, num_runs=8)))
    P = queue.m_times.shape[0]
    hlo = jax.jit(_merge_runs_into_main).lower(queue).compile().as_text()
    sorts = [line for line in hlo.splitlines() if " sort(" in line]
    assert sorts, "the pool is no longer sorted: check this test's premise"
    for line in sorts:
        lengths = [int(d) for d in re.findall(r"\[(\d+)", line.split("=")[1])]
        assert max(lengths) < P, line
