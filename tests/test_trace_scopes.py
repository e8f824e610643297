"""The engine's named device scopes and host spans (``repro.core.spans``).

* The lowered ``engine.run`` of a tiered3 program names every super-step
  leg in its op metadata, in a closed build and in a spill (fenced)
  build; the jitted arrival absorb names ``des.absorb``.
* A streamed run under ``jax.profiler`` records one ``des.segment`` per
  ``engine.run`` call, one ``des.boundary`` per boundary (one more than
  the segments: before the first, between each two, after the last), and
  ``des.absorb`` spans whose ``rows`` sum to the run's ``ingested``;
  the feeder thread's ``des.feeder.stage`` spans stay off the loop's
  thread.
* The profiled run's result is bit-identical to the same run without it.
"""

import glob
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import spans
from repro.core.program import Config
from repro.serving.scenarios import (
    build_open_admission_program,
    initial_state,
)
from repro.stream import PoissonSource

N_REQ = 40
SLOTS = 4
LEGS = (spans.EXTRACT, spans.DISPATCH, spans.INSERT, spans.MERGE)


def _build(**kw):
    prog = build_open_admission_program(
        num_slots=SLOTS, num_requests=N_REQ, max_decode=5,
        config=Config(max_batch_len=3, capacity=256, max_emit=2))
    return prog.build(backend="device", **kw)


def _source():
    return PoissonSource(1.5, N_REQ, seed=42, grid=0.25, t0=0.0,
                         type_id=0, block_size=16)


def _scopes_in(hlo_text: str) -> Counter:
    """Innermost ``des.*`` scope of each op name in lowered text."""
    found = Counter()
    for name in re.findall(r'"([^"]*des\.[^"]*)"', hlo_text):
        found[re.findall(r"des\.[a-z_.]*[a-z_]", name)[-1]] += 1
    return found


@pytest.mark.parametrize("overflow", ["drop", "spill"])
def test_lowered_run_names_every_leg(overflow):
    sim = _build(overflow=overflow)
    eng = sim.engine
    stats = eng.initial_run_stats()
    assert ("bound_t" in stats) == (overflow == "spill")  # fenced build
    low = eng.lower_run(initial_state(SLOTS), eng.initial_queue([]))
    found = _scopes_in(low.as_text(debug_info=True))
    for leg in LEGS:
        assert found[leg] > 0, (leg, found)
    assert found[spans.ABSORB] == 0


def test_lowered_absorb_names_absorb():
    sim = _build()
    rows = jnp.zeros((16, 6), jnp.float32)
    low = sim._absorb_fn().lower(
        sim.engine.initial_queue([]), rows, jnp.zeros((16,), jnp.int32),
        jnp.int32(0), jnp.int32(4))
    found = _scopes_in(low.as_text(debug_info=True))
    assert found[spans.ABSORB] > 0
    # the absorb reaches the merge through its preflush: innermost wins
    assert found[spans.MERGE] > 0


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """The same streamed run without and with the profiler, counting
    the engine's ``run`` calls of the profiled one."""
    from jax.profiler import ProfileData

    sim = _build()
    plain = sim.run(initial_state(SLOTS), arrivals=_source())
    calls = []
    run = sim.engine.run

    def counted(*a, **kw):
        calls.append(1)
        return run(*a, **kw)

    sim.engine.run = counted
    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        traced = sim.run(initial_state(SLOTS), arrivals=_source())
    finally:
        jax.profiler.stop_trace()
        del sim.engine.run
    path = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)[0]
    threads = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, dict(e.stats)) for e in line.events
                       if e.name.startswith("des.")]
                if evs:
                    threads.append(evs)
    return plain, traced, len(calls), threads


def test_streamed_run_records_segments_boundaries_and_absorbs(profiled):
    _, res, n_calls, threads = profiled
    loop = [t for t in threads if any(n == spans.SEGMENT for n, _ in t)]
    assert len(loop) == 1
    names = Counter(n for n, _ in loop[0])
    assert n_calls > 1
    assert names[spans.SEGMENT] == n_calls
    assert names[spans.BOUNDARY] == n_calls + 1
    rows = [a["rows"] for n, a in loop[0] if n == spans.ABSORB]
    assert rows and sum(rows) == res.ingested == N_REQ
    assert names[spans.FENCE] == names[spans.OCCUPANCY] == len(rows)
    assert names[spans.FEEDER_STAGE] == 0
    staged = sum(n == spans.FEEDER_STAGE for t in threads for n, _ in t)
    assert staged >= N_REQ // 16


def test_profiled_run_is_bit_identical(profiled):
    plain, traced, _, _ = profiled
    for key in ("events", "batches", "final_time", "emitted", "pending",
                "ingested", "dropped", "spilled"):
        assert getattr(traced, key) == getattr(plain, key), key
    for k, v in plain.state.items():
        np.testing.assert_array_equal(np.asarray(traced.state[k]),
                                      np.asarray(v), err_msg=k)
