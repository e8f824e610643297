"""The segment driver's one compiled read per boundary.

* ``DeviceEngine.boundary_read`` returns the stats' ``batches`` and
  ``spill_n`` and the queue's occupancy and earliest pending time, equal
  to the eager queue ops, on every queue layout the driver meets: an
  empty tiered3 queue, a drained front over live runs and staged rows,
  a full front, the other queue modes, the sharded engine's tuple of
  shards and its stacked layout (unplaced, and placed on four forced
  host devices).
* A driven run (streamed, spill, streamed with spill, checkpointed,
  sharded) makes one ``engine.run`` call per boundary that
  ``RunResult.boundaries`` counts, one compiled read after each call,
  one before the first and one after each spill reabsorb, and no eager
  queue read at all; the result is bit-identical to the same run
  without the instrumentation.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Config, PoissonSource, SimProgram
from repro.core import DeviceEngine, EventRegistry
from repro.core import engine as E
from repro.core import queue as Q
from repro.core import sharded as S
from repro.serving.scenarios import (
    build_open_admission_program,
    initial_state,
)
from repro.testing.faults import tiny_phold


def _registry():
    reg = EventRegistry()
    reg.register("NOP", lambda state, t, arg: state, lookahead=0.5)
    return reg.freeze()


def _events(n, seed=0):
    rng = np.random.default_rng(seed)
    return [(0.25 * int(rng.integers(1, 4 * n)), 0,
             np.asarray([i % 5, 0, 0, 0], np.float32)) for i in range(n)]


def _drained_front(eng):
    """Front empty; the earliest key sits in a run's live remainder
    (its consumed head holds an earlier, stale time), with rows staged
    and the ring holding later ones: the ``_run_mins`` fallback."""
    q = Q.tiered3_queue_init(eng.capacity, front_cap=eng.front_cap,
                             stage_cap=eng.stage_cap, num_runs=eng.num_runs)
    R, St = eng.num_runs, eng.stage_cap
    r_times = np.full((R, St), np.inf, np.float32)
    r_types = np.full((R, St), -1, np.int32)
    r_times[0, :3] = [0.5, 1.25, 4.0]   # head consumed: live from 1.25
    r_types[0, :3] = 0
    r_times[1, :2] = [2.0, 3.0]
    r_types[1, :2] = 0
    s_times = np.full((St,), np.inf, np.float32)
    s_types = np.full((St,), -1, np.int32)
    s_times[:2] = [1.5, 2.5]
    s_types[:2] = 0
    P = q.main_phys
    m_times = np.full((P,), np.inf, np.float32)
    m_types = np.full((P,), -1, np.int32)
    m_times[3:6] = [1.75, 5.0, 6.0]
    m_types[3:6] = 0
    return q._replace(
        r_times=jnp.asarray(r_times), r_types=jnp.asarray(r_types),
        r_off=jnp.asarray([1] + [0] * (R - 1), jnp.int32),
        r_len=jnp.asarray([3, 2] + [0] * (R - 2), jnp.int32),
        s_times=jnp.asarray(s_times), s_types=jnp.asarray(s_types),
        stage_n=jnp.int32(2),
        m_times=jnp.asarray(m_times), m_types=jnp.asarray(m_types),
        m_head=jnp.int32(3), main_n=jnp.int32(3), front_n=jnp.int32(0),
        size=jnp.int32(9),
    )


def _single(mode="tiered3"):
    kw = dict(queue_mode=mode, max_batch_len=2, capacity=32)
    if mode == "tiered3":
        kw.update(front_cap=4, stage_cap=4, num_runs=3)
    elif mode == "tiered":
        kw.update(front_cap=4, stage_cap=4)
    return DeviceEngine(_registry(), **kw)


def _sharded(placement="serial"):
    return S.ShardedDeviceEngine(
        _registry(), shards=4, placement=placement, max_batch_len=2,
        capacity=48, front_cap=4, stage_cap=4, num_runs=2)


def _eager_next_time(eng, queue):
    if isinstance(eng, S.ShardedDeviceEngine):
        return min(float(Q.tiered3_queue_next_time(q))
                   for q in queue.shards)
    return float({
        "tiered3": Q.tiered3_queue_next_time,
        "tiered": Q.tiered_queue_next_time,
        "flat": Q.device_queue_next_time_ref,
        "reference": Q.device_queue_next_time_ref,
    }[eng.queue_mode](queue))


def _eager_occupancy(eng, queue):
    if isinstance(eng, S.ShardedDeviceEngine):
        return sum(int(Q.tiered3_queue_occupancy(q)) for q in queue.shards)
    if eng.queue_mode == "tiered3":
        return int(Q.tiered3_queue_occupancy(queue))
    if eng.queue_mode == "tiered":
        return int(Q.tiered_queue_occupancy(queue))
    return int(np.sum(np.asarray(queue.types) >= 0))


def _case(name):
    """``(engine, queue, stats, expected occupancy)``."""
    counters = {"batches": jnp.int32(7), "spill_n": jnp.int32(3)}
    if name == "empty":
        eng = _single()
        return eng, eng.initial_queue([]), None, 0
    if name == "drained_front":
        eng = _single()
        return eng, _drained_front(eng), counters, 9
    if name == "full_front":
        eng = _single()
        q = eng.initial_queue(_events(20))
        assert int(q.front_n) == eng.front_cap
        return eng, q, {"batches": jnp.int32(2)}, 20
    if name in ("tiered", "flat", "reference"):
        eng = _single(name)
        return eng, eng.initial_queue(_events(12, 1)), counters, 12
    if name == "serial_shards":
        eng = _sharded()
        return eng, eng.initial_queue(_events(30, 2)), counters, 30
    if name == "stacked_shards":
        eng = _sharded()
        q = S.stack_sharded_queue(eng.initial_queue(_events(30, 3)))
        return eng, q, counters, 30
    raise KeyError(name)


def _check_read(name):
    eng, queue, stats, occ = _case(name)
    got = eng.boundary_read(queue, stats)
    assert isinstance(got, E.BoundaryRead)
    src = eng.initial_run_stats() if stats is None else stats
    assert got.batches == int(src["batches"])
    assert got.spill_n == int(src.get("spill_n", 0))
    assert got.occupancy == _eager_occupancy(eng, queue) == occ
    want_t = _eager_next_time(eng, queue)
    assert got.next_time == want_t
    if name == "empty":
        assert got.next_time == float("inf")
    if name == "drained_front":
        assert got.next_time == 1.25
    return got


_PLACED = """
import sys
sys.path.insert(0, {here!r})
from test_boundary_read import _check_placed
_check_placed()
"""


def _check_placed():
    """The stacked layout placed one shard per device: run in a child
    with four forced host devices (the parent's JAX is already up)."""
    eng = _sharded("devices")
    queue = eng.initial_queue(_events(30, 4))
    assert isinstance(queue, S.StackedShardedQueue)
    got = eng.boundary_read(queue, {"batches": jnp.int32(5)})
    assert got.batches == 5 and got.spill_n == 0
    # the eager ops cannot slice a shard off the mesh: read a host copy
    host = jax.tree_util.tree_map(np.asarray, queue)
    assert got.occupancy == _eager_occupancy(eng, host) == 30
    assert got.next_time == _eager_next_time(eng, host)


@pytest.mark.parametrize("name", [
    "empty", "drained_front", "full_front", "tiered", "flat", "reference",
    "serial_shards", "stacked_shards", "stacked_placed",
])
def test_boundary_read_matches_eager_ops(name):
    if name != "stacked_placed":
        _check_read(name)
        return
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(here), "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", _PLACED.format(here=here)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]


# -- the segment loop's contract -------------------------------------------

def _storm(cap):
    """A population that outgrows a small queue: spills mid-run."""
    p = SimProgram("storm", config=Config(
        max_batch_len=2, capacity=cap, max_emit=2))

    @p.handler("GEN", lookahead=0.1, emits=True)
    def gen(state, t, arg):
        alive = t < 2.0
        e = jnp.full((2, 6), -1.0, jnp.float32).at[:, 0].set(0.0)
        e = e.at[0, 0].set(jnp.where(alive, 0.3, -1.0))
        e = e.at[0, 1].set(jnp.where(alive, 0.0, -1.0))
        e = e.at[1, 0].set(jnp.where(alive, 0.45, -1.0))
        e = e.at[1, 1].set(jnp.where(alive, 0.0, -1.0))
        return state + 1, e

    for i in range(6):
        p.schedule(0.05 * i, "GEN")
    return p


def _sink(cap):
    p = SimProgram("sink", config=Config(
        max_batch_len=4, capacity=cap, max_emit=1))

    @p.handler("SINK", lookahead=0.25)
    def sink(state, t, arg):
        return state + 1

    p.schedule(0.0, "SINK")
    p.schedule(0.25, "SINK")
    return p


def _run_case(name, tmp_path):
    """``(sim, run)``: a fresh build and a thunk driving one run of it."""
    if name == "streamed":
        sim = build_open_admission_program(
            num_slots=4, num_requests=40, max_decode=5,
            config=Config(max_batch_len=3, capacity=256, max_emit=2),
        ).build(backend="device")
        return sim, lambda: sim.run(initial_state(4), arrivals=PoissonSource(
            1.5, 40, seed=42, grid=0.25, t0=0.0, type_id=0, block_size=16))
    if name == "spill":
        sim = _storm(16).build(backend="device", overflow="spill")
        return sim, lambda: sim.run(jnp.int32(0))
    if name == "streamed_spill":
        sim = _sink(8).build(backend="device", overflow="spill")
        return sim, lambda: sim.run(
            jnp.int32(0), max_batches=200,
            arrivals=PoissonSource(4.0, 32, grid=0.25, type_id=0))
    if name == "checkpointed":
        sim = tiny_phold().build(backend="device")
        return sim, lambda: sim.run(
            jnp.int32(0), max_batches=24, checkpoint_every=5,
            checkpoint_dir=str(tmp_path / name))
    if name == "sharded":
        sim = tiny_phold(capacity=64).build(backend="device", shards=2)
        return sim, lambda: sim.run(
            jnp.int32(0), max_batches=40, arrivals=PoissonSource(
                2.0, 24, grid=0.25, type_id=0, block_size=8))
    raise KeyError(name)


def _traced_only(fn):
    """``fn`` that fails when its queue holds concrete arrays: an eager
    queue op rather than a step of a compiled program."""
    def guard(q, *a, **kw):
        if not any(isinstance(x, jax.core.Tracer)
                   for x in jax.tree_util.tree_leaves(q)):
            raise AssertionError(f"eager {fn.__name__} in the driver")
        return fn(q, *a, **kw)
    return guard


_FIELDS = ("events", "batches", "final_time", "emitted", "pending",
           "ingested", "spilled", "shed", "dropped")


@pytest.mark.parametrize("name", [
    "streamed", "spill", "streamed_spill", "checkpointed", "sharded",
])
def test_one_compiled_read_per_boundary(name, tmp_path, monkeypatch):
    sim, run = _run_case(name, tmp_path)
    plain = run()

    eng = sim.engine
    counts = {"run": 0, "read": 0, "reabsorb": 0}
    run_call, read_call = eng.run, eng.boundary_read
    absorb_spill = sim._absorb_spill

    def counted_run(*a, **kw):
        counts["run"] += 1
        return run_call(*a, **kw)

    def counted_read(*a, **kw):
        counts["read"] += 1
        return read_call(*a, **kw)

    def counted_absorb_spill(*a, **kw):
        counts["reabsorb"] += 1
        return absorb_spill(*a, **kw)

    eng.run, eng.boundary_read = counted_run, counted_read
    sim._absorb_spill = counted_absorb_spill
    for mod in (Q, E, S):
        for fn in ("tiered3_queue_next_time", "tiered3_queue_occupancy"):
            if hasattr(mod, fn):
                monkeypatch.setattr(mod, fn, _traced_only(getattr(mod, fn)))
    res = run()

    assert counts["run"] > 1
    assert res.boundaries == counts["run"]
    assert counts["read"] == res.boundaries + 1 + counts["reabsorb"]
    if "spill" in name:
        assert counts["reabsorb"] > 0
    for key in _FIELDS:
        assert getattr(res, key) == getattr(plain, key), key
    for a, b in zip(jax.tree_util.tree_leaves(res.state),
                    jax.tree_util.tree_leaves(plain.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
