"""Beyond-paper: fully on-device DES vs host-driven dispatch, plus the
per-batch scheduling-overhead split (extract / dispatch / insert).

Two measurements:

* ``run``  — events/second of the on-device engine against the
  host-driven batched scheduler on the PoC model (as in the seed).

* ``scheduling_overhead`` — the cost of the queue machinery itself, on
  a trivial-handler workload (each event bumps a counter and emits one
  far-future event, so per-batch time is almost pure scheduling).
  Two measurements:

  - **anchor** (capacity 4096, max_batch_len 16, the PR-1 reference
    point): whole-run per-batch and per-op split for all four queue
    modes (tiered3 / tiered / flat / reference).

  - **capacity sweep** (1k/4k/16k/64k × {tiered3, tiered, flat}) at a
    FIXED pending-set size, so what scales is only the allocated
    capacity:
    whole-run per-batch cost plus a chained insert-op loop.  The
    recorded ``insert_op_ratio_16k_over_1k`` is the capacity-
    independence claim as a number: per-batch insert cost at 16384
    must stay within 2x of its capacity-1024 cost under
    ``queue_mode="tiered"``.

* ``near_full`` — the worst-case stress: the queue held at >=90%
  occupancy with emissions alternating between near-head landings
  (front merges + tail evictions into staging) and far-future landings
  (staging appends with no ring headroom), so the two-tier queue's
  O(capacity) flush/merge/compaction paths fire continuously.  This is
  the workload the log-structured ``tiered3`` mode exists for; the
  section records all of tiered3/tiered/flat at the anchor capacity
  plus a tiered3-vs-tiered CAPACITY SWEEP of the same workload (the
  "worst-case path no longer scales with capacity" claim as numbers).
  ``--near-full-only`` refreshes just this section of the JSON, and
  ``--check-baseline R`` instead compares the fresh tiered3 median
  against the recorded baseline, failing (exit 1) on a >R× regression
  — the CI perf gate.

* ``fused_dispatch`` (``--fused-only``) — the composition-specialized
  dispatch (DESIGN.md §7): whole-run per-batch cost AND a chained
  per-dispatch microbenchmark on the hottest observed word (profiled
  via ``RunResult.word_counts``) for all three dispatch modes, on the
  PoC model and the serving admission scenario.  The claim the section
  records is *hot-word fused dispatch <= the generic masked path* —
  the bounded W+1-way switch plus straight-line super-procedures must
  not cost more than the per-lane type switches they replace.
  ``--fused-only --check-baseline R`` gates the fused/masked
  per-dispatch ratio against the recorded baseline (same
  machine-independence reasoning as the near-full gate).

* ``fused_dispatch_static`` (also under ``--fused-only``) — fused
  dispatch with the static analyzer's compile-time hot set
  (``hot_words="static"``, DESIGN.md §11) against the top-W profiled
  words on the same workloads.  Results are bit-identical by
  construction (asserted); the section records the per-batch cost of
  skipping the profiling run and the hot-set coverage of each choice.
  The same ``--check-baseline R`` invocation gates the
  static/profiled per-batch ratio.

* ``streaming`` (``--streaming-only``) — the open-system serving axis
  (DESIGN.md §10): sustained requests/second streaming a Poisson trace
  through ``run(arrivals=...)`` on the admission scenario, against the
  pre-seeded closed reference (bit-identity checked), plus the
  double-buffer A/B (prefetch vs ``_stream_prefetch=False`` on a
  decode-bound source) and a bounded-memory ``overflow='spill'``
  variant.  ``--check-streaming R`` gates bit-identity and the
  streamed/pre-seeded wall ratio (absolute ceiling, both sides fresh);
  ``--trace PATH`` replays a ``scripts/gen_trace.py`` file at
  acceptance scale into the ``trace_replay`` subsection.

* ``shards_sweep`` (``--shards-only``) — the sharded engine
  (DESIGN.md §5.1) against the bit-identical single tiered3 queue on
  the 92%-occupancy ROUTED churn (re-emits hop entities, so a constant
  fraction crosses shard boundaries): per-super-step cost for shards
  ∈ {1, 2, 4} at each capacity, interleaved A/B rounds.  Since every
  super-step executes exactly the single-queue window, the recorded
  ratio IS the merge/exchange overhead of the sharded machinery.

Whole-run timings are median-of-N (``--repeats``, default 5) with the
raw samples recorded next to every median: single-shot numbers on
shared CPU runners are ±30% noisy, which is exactly the band a
near-full regression has to clear.  Per-op microbenchmarks keep their
min-of-5 chained-loop form.  NOTE (PR 4): the ``reference`` insert
column times :func:`device_queue_push_rows`, now a one-pass scatter
that is bit-identical to — but much faster than — the serial seed
chain it replaced, so pre-PR-4 ``reference`` insert numbers are not
comparable; ``reference`` extraction is unchanged (the serial spec).

  Results land in ``BENCH_device_engine.json`` at the repo root so
  future PRs have a perf trajectory to track.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import poc
from repro.core import (
    DeviceEngine,
    EventRegistry,
    ShardedDeviceEngine,
    Simulator,
    emits_events,
)
from repro.core.events import ARG_WIDTH
from repro.core.queue import (
    device_queue_extract,
    device_queue_extract_ref,
    device_queue_fill_rows,
    device_queue_push_rows,
    tiered3_queue_extract,
    tiered3_queue_fill_rows,
    tiered_queue_extract,
    tiered_queue_fill_rows,
)

JSON_PATH = Path(__file__).resolve().parents[1] / "BENCH_device_engine.json"


def _deep_merge(base: dict, fresh: dict) -> dict:
    """Recursive loss-free merge: fresh leaves replace, fresh dicts
    recurse, keys present only in ``base`` always survive.

    Every write to the recorded JSON goes through this.  The shallow
    ``dict.update`` it replaces lost nested sections: a full ``main()``
    run rebuilt ``scheduling_overhead`` from scratch and wholesale-
    replaced the recorded object, erasing any subsection recorded by a
    ``--*-only`` pass that the fresh dict did not recompute (the
    ``streaming.trace_replay`` special case was a symptom-level patch
    for one instance of exactly this)."""
    out = dict(base)
    for k, v in fresh.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def _merge_into_json(fresh: dict) -> None:
    """Deep-merge ``fresh`` into the recorded baseline JSON."""
    payload = json.loads(JSON_PATH.read_text()) if JSON_PATH.exists() \
        else {}
    JSON_PATH.write_text(
        json.dumps(_deep_merge(payload, fresh), indent=2) + "\n")
# What a failing baseline gate tells the operator to run: the recorded
# sections compared by --check-baseline come from a full (no --quick)
# fused-only pass.
_REGEN_FUSED = ("re-record with: python benchmarks/device_engine.py "
                "--fused-only")


def run(quick: bool = False):
    iters = 2_000 if quick else 20_000
    num_events = 128 if quick else 384
    n = 4
    rng = np.random.default_rng(0)
    types = [int(x) for x in (rng.random(num_events) < 0.5)]

    # host engine
    reg = poc.build_registry(iters=iters)
    sim = Simulator(reg, max_batch_len=n)
    for t, ty in enumerate(types):
        sim.queue.push(float(t), ty)
    state, _ = sim.run(poc.initial_state(), mode="conservative")  # warm
    sim2 = Simulator(reg, max_batch_len=n)
    sim2.composer = sim.composer
    for t, ty in enumerate(types):
        sim2.queue.push(float(t), ty)
    t0 = time.perf_counter()
    state_h, _ = sim2.run(poc.initial_state(), mode="conservative")
    jax.block_until_ready(state_h)
    t_host = time.perf_counter() - t0

    # on-device engine
    eng = DeviceEngine(reg, max_batch_len=n, capacity=num_events + 8)
    queue = eng.initial_queue([(float(t), ty, None)
                               for t, ty in enumerate(types)])
    eng.run(poc.initial_state(), queue)  # warm (compiles)
    queue = eng.initial_queue([(float(t), ty, None)
                               for t, ty in enumerate(types)])
    t0 = time.perf_counter()
    state_d, _q, stats = eng.run(poc.initial_state(), queue)
    jax.block_until_ready(state_d)
    t_dev = time.perf_counter() - t0

    assert int(state_h) == int(state_d) == poc.reference_final_sum(
        types, iters)
    return {
        "events": num_events,
        "host_us_per_event": t_host / num_events * 1e6,
        "device_us_per_event": t_dev / num_events * 1e6,
        "device_speedup": t_host / t_dev,
    }


def _trivial_registry():
    """One trivial emitting type: bump a counter, emit one event far in
    the future (keeps the queue at steady occupancy, so every batch
    pays full-queue scheduling cost)."""
    reg = EventRegistry()

    @emits_events
    def tick(state, t, arg):
        emit = jnp.zeros((1, 2 + ARG_WIDTH), jnp.float32)
        emit = emit.at[0, 0].set(t + 1e6).at[0, 1].set(0.0)
        return state + 1, emit

    reg.register("Tick", tick, lookahead=1e6)
    return reg.freeze()


def _bench_op_loop(step, init, iters):
    """µs per application of ``step``, chained in one jitted fori_loop
    (matches how the ops run inside the engine — per-call dispatch
    overhead would otherwise dominate and invert the comparison).

    Short chains (small ``iters``) are re-launched enough times per
    timing sample to keep each sample above ~1k steps; min over 5
    samples filters scheduler noise.
    """
    looped = jax.jit(
        lambda init: jax.lax.fori_loop(0, iters, lambda i, c: step(c), init)
    )
    jax.block_until_ready(looped(init))
    launches = max(1, -(-1024 // iters))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(launches):
            out = looped(init)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / (iters * launches))
    return best * 1e6


def _bench_ops_interleaved(steps, init, iters, rounds=7):
    """_bench_op_loop over several candidate step fns at once, timed
    round-robin (one sample each per round) so host-load drift hits
    every candidate equally — the gates compare the RATIOS
    (DESIGN.md §6.4), and sequential blocks would let a load spike
    land entirely on one candidate."""
    looped = {
        name: jax.jit(lambda init, f=f: jax.lax.fori_loop(
            0, iters, lambda i, c: f(c), init))
        for name, f in steps.items()
    }
    for fn in looped.values():
        jax.block_until_ready(fn(init))
    launches = max(1, -(-1024 // iters))
    best = {name: float("inf") for name in steps}
    for _ in range(rounds):
        for name, fn in looped.items():
            t0 = time.perf_counter()
            for _ in range(launches):
                out = fn(init)
            jax.block_until_ready(out)
            best[name] = min(
                best[name], (time.perf_counter() - t0) / (iters * launches))
    return {name: v * 1e6 for name, v in best.items()}


def _time_engines_interleaved(runs, max_batches, repeats=5):
    """Round-robin median-of-``repeats`` µs/batch for several engines.

    ``runs`` maps label -> (engine, events).  One sample per engine per
    round, cycling through the engines, so slow phases of a shared/
    noisy host hit every mode roughly equally — the A/B comparison
    stays trustworthy even when absolute numbers drift between rounds.
    Two warm runs per engine first: one covers compilation, the second
    the allocator/cache warm-up that otherwise penalizes whichever
    engine is timed first.  Returns label -> (median, samples).
    """
    for eng, events in runs.values():
        for _ in range(2):  # compile + allocator warm-up
            q = eng.initial_queue(events)
            eng.run(jnp.int32(0), q, max_batches=max_batches)
    samples = {label: [] for label in runs}
    for _ in range(max(1, repeats)):
        for label, (eng, events) in runs.items():
            q = eng.initial_queue(events)
            t0 = time.perf_counter()
            s, _q, stats = eng.run(jnp.int32(0), q,
                                   max_batches=max_batches)
            jax.block_until_ready(s)
            samples[label].append((time.perf_counter() - t0)
                                  / int(stats["batches"]) * 1e6)
    return {label: (float(np.median(v)), v)
            for label, v in samples.items()}


def _time_engine_run(eng, events, max_batches, repeats=5):
    """Median-of-``repeats`` µs/batch for a whole engine run, plus the
    raw per-sample values (kept in the JSON so the medians can be
    re-judged against the run-to-run noise they were taken in).
    The single-engine case of :func:`_time_engines_interleaved` — one
    warm-up/sampling protocol, defined once."""
    return _time_engines_interleaved(
        {"only": (eng, events)}, max_batches, repeats)["only"]


def _advancing_rows(max_len):
    """One full emit block per iteration, timestamps marching forward
    (the common DES shape — keeps the tiered staging on its append
    path, as a real emitting workload would)."""
    rows = np.full((max_len, 2 + ARG_WIDTH), -1.0, np.float32)
    rows[:, 0] = np.arange(max_len, dtype=np.float32)
    rows[:, 1] = 0.0
    return jnp.asarray(rows)


def _insert_op_us(eng, mode, events, max_len, base_t, in_iters):
    """µs per chained emit-block insert starting from ``events`` pending.

    ``in_iters`` must keep ``len(events) + in_iters * max_len`` within
    capacity; callers pass the SAME count across a capacity sweep so
    fixed loop overhead cancels out of the comparison.
    """
    q0 = eng.initial_queue(events)
    rows = _advancing_rows(max_len)
    fill = {"tiered": tiered_queue_fill_rows,
            "tiered3": tiered3_queue_fill_rows,
            "flat": device_queue_fill_rows,
            "reference": device_queue_push_rows}[mode]

    def step(carry):
        i, q = carry
        block = rows.at[:, 0].add(base_t + i * max_len)
        return i + 1, fill(q, block)

    return _bench_op_loop(step, (jnp.int32(0), q0), in_iters)


def scheduling_overhead(quick: bool = False, repeats: int = 5):
    max_len = 16
    max_batches = 128 if quick else 512

    # -- anchor: the PR-1 reference point, all four queue modes --------
    capacity = 1024 if quick else 4096
    num_events = capacity - 2 * max_len
    events = [(float(t), 0, None) for t in range(num_events)]

    per_batch = {}
    samples = {}
    engines = {}
    for mode in ("tiered3", "tiered", "flat", "reference"):
        eng = DeviceEngine(_trivial_registry(), max_batch_len=max_len,
                           capacity=capacity, max_emit=1, queue_mode=mode)
        engines[mode] = eng
        per_batch[mode], samples[mode] = _time_engine_run(
            eng, events, max_batches, repeats)

    # Per-op split: each op chained in its own fused loop, from a
    # representative steady state.
    eng = engines["flat"]
    la = eng._lookaheads
    q_full = eng.initial_queue(events)
    tq_full = engines["tiered"].initial_queue(events)
    t3q_full = engines["tiered3"].initial_queue(events)
    _, ts, tys, args, length = device_queue_extract(q_full, max_len, la)
    code = eng.codec.encode_jnp(tys, length)
    half = events[: num_events // 2]

    # Iteration counts keep the extract loops from draining the queues
    # and the insert loops from overflowing them.
    ex_iters = max(1, (num_events - max_len) // max_len)
    phase = {
        "extract": {
            "tiered3": _bench_op_loop(
                lambda q: tiered3_queue_extract(q, max_len, la)[0],
                t3q_full, ex_iters),
            "tiered": _bench_op_loop(
                lambda q: tiered_queue_extract(q, max_len, la)[0],
                tq_full, ex_iters),
            "flat": _bench_op_loop(
                lambda q: device_queue_extract(q, max_len, la)[0],
                q_full, ex_iters),
            "reference": _bench_op_loop(
                lambda q: device_queue_extract_ref(q, max_len, la)[0],
                q_full, ex_iters),
        },
        "insert": {
            mode: _insert_op_us(
                engines[mode], mode, half, max_len, float(num_events),
                max(1, (capacity - num_events // 2 - max_len) // max_len))
            for mode in ("tiered3", "tiered", "flat", "reference")
        },
        "dispatch": {
            "shared": _bench_op_loop(
                lambda s: eng.dispatch(code, s, ts, tys, args)[0],
                jnp.int32(0), 256),
        },
    }

    anchor = {
        "capacity": capacity,
        "max_batch_len": max_len,
        "num_seed_events": num_events,
        "batches_timed": max_batches,
        "repeats": repeats,
        "per_batch_us": {
            **per_batch,
            "speedup_tiered_vs_reference":
                per_batch["reference"] / per_batch["tiered"],
            "speedup_tiered_vs_flat":
                per_batch["flat"] / per_batch["tiered"],
            "speedup_tiered3_vs_reference":
                per_batch["reference"] / per_batch["tiered3"],
        },
        "per_batch_samples_us": samples,
        "per_op_us": phase,
    }

    # -- capacity sweep: fixed pending-set size, growing capacity ------
    sweep_caps = [1024, 4096] if quick else [1024, 4096, 16384, 65536]
    sweep_events = [(float(t), 0, None) for t in range(1000)]
    insert_base = sweep_events[:256]
    # Identical iteration count at every capacity (sized so the
    # SMALLEST capacity cannot overflow): fixed loop overhead cancels.
    sweep_iters = (min(sweep_caps) - len(insert_base) - max_len) // max_len
    sweep = {}
    for cap in sweep_caps:
        row = {}
        for mode in ("tiered3", "tiered", "flat"):
            eng = DeviceEngine(_trivial_registry(), max_batch_len=max_len,
                               capacity=cap, max_emit=1, queue_mode=mode)
            med, raw = _time_engine_run(eng, sweep_events, max_batches,
                                        repeats)
            row[mode] = {
                "per_batch_us": med,
                "per_batch_samples_us": raw,
                "insert_op_us": _insert_op_us(
                    eng, mode, insert_base, max_len, 1000.0, sweep_iters),
            }
        sweep[str(cap)] = row

    def ratio(mode, hi, lo):
        if str(hi) in sweep and str(lo) in sweep:
            return (sweep[str(hi)][mode]["insert_op_us"]
                    / sweep[str(lo)][mode]["insert_op_us"])
        return None

    result = {
        "workload": {
            "description": "trivial emitting handler (counter + 1 far-future"
                           " emit); per-batch time ~= scheduling overhead",
            "max_batch_len": max_len,
            "max_emit": 1,
            "batches_timed": max_batches,
            "repeats": repeats,
        },
        "anchor": anchor,
        "capacity_sweep": {
            "fixed_pending_events": 1000,
            "insert_loop": {"base_pending": len(insert_base),
                            "iters": sweep_iters},
            "capacities": sweep,
            "insert_op_ratio_16k_over_1k": ratio("tiered", 16384, 1024),
            "insert_op_ratio_64k_over_1k": ratio("tiered", 65536, 1024),
            "tiered3_insert_op_ratio_16k_over_1k":
                ratio("tiered3", 16384, 1024),
            "tiered3_insert_op_ratio_64k_over_1k":
                ratio("tiered3", 65536, 1024),
        },
    }
    return result


def _churn_registry(near_delay: float):
    """Emitting type for the near-full stress: each event re-emits with
    a timestamp alternating (by 16-event stripe) between *just past the
    current window* — lands in the front tier, forcing merges and tail
    evictions — and *far future* — lands in staging/main with no ring
    headroom left.  Both legs push the tiered queue onto its rare
    O(capacity) flush/merge paths every few batches."""
    reg = EventRegistry()

    @emits_events
    def churn(state, t, arg):
        far = jnp.floor(t / 16.0) % 2.0 == 0.0
        delay = jnp.where(far, jnp.float32(1e6), jnp.float32(near_delay))
        emit = jnp.zeros((1, 2 + ARG_WIDTH), jnp.float32)
        emit = emit.at[0, 0].set(t + delay).at[0, 1].set(0.0)
        return state + 1, emit

    reg.register("Churn", churn, lookahead=1e6)
    return reg.freeze()


def near_full(quick: bool = False, repeats: int = 5, sweep: bool = True,
              controls: bool = True):
    """The queue at >=90% occupancy under sustained flush pressure.

    Occupancy is stationary (each batch pops ``max_len`` events and
    inserts ``max_len`` emissions), so the whole timed run sits at the
    seeded fraction.  Anchor capacity: tiered3/tiered/flat medians plus
    a low-occupancy control of the identical workload (the penalty is
    the pressure, not the handler).  Capacity sweep (tiered3 vs
    tiered): the same 92%-occupancy workload at every capacity — the
    number that must stay flat for tiered3 and grows for the two-tier
    flush merge.  ``sweep=False`` skips it (the CI gate reads only the
    anchor, and every sweep capacity costs fresh compiles + timed
    runs); ``controls=False`` likewise skips the low-occupancy
    control runs the gate never reads.
    """
    max_len = 16
    capacity = 1024 if quick else 4096
    max_batches = 128 if quick else 512
    occupancy = 0.92

    def seeded(cap, frac):
        return [(float(t), 0, None) for t in range(int(cap * frac))]

    def engine(mode, cap):
        return DeviceEngine(_churn_registry(near_delay=17.0),
                            max_batch_len=max_len, capacity=cap,
                            max_emit=1, queue_mode=mode)

    engines = {mode: engine(mode, capacity)
               for mode in ("tiered3", "tiered", "flat")}
    # Interleaved rounds: host-load drift hits every mode equally, so
    # the mode-vs-mode comparison survives a noisy box.
    timed = _time_engines_interleaved(
        {m: (engines[m], seeded(capacity, occupancy)) for m in engines},
        max_batches, repeats)
    per_batch = {m: t[0] for m, t in timed.items()}
    samples = {m: t[1] for m, t in timed.items()}
    # Low-occupancy controls on the SAME compiled engines (engines are
    # re-runnable; only the seeded queue differs).
    low = None
    if controls:
        low = {
            m: t[0]
            for m, t in _time_engines_interleaved(
                {m: (engines[m], seeded(capacity, 0.25))
                 for m in ("tiered3", "tiered")},
                max_batches, repeats).items()
        }

    sweep_caps = [1024, 4096] if quick else [1024, 4096, 16384, 65536]
    rows = {}
    if sweep:
        for cap in sweep_caps:
            timed = _time_engines_interleaved(
                {m: (engines[m] if cap == capacity else engine(m, cap),
                     seeded(cap, occupancy))
                 for m in ("tiered3", "tiered")},
                max_batches, repeats)
            rows[str(cap)] = {
                m: {"per_batch_us": t[0], "per_batch_samples_us": t[1]}
                for m, t in timed.items()
            }

    def ratio(mode, hi, lo):
        if str(hi) in rows and str(lo) in rows:
            return (rows[str(hi)][mode]["per_batch_us"]
                    / rows[str(lo)][mode]["per_batch_us"])
        return None

    return {
        "description": "alternating near-head/far-future re-emits at "
                       "stationary >=90% occupancy; sustains the two-tier "
                       "queue's O(capacity) flush/merge/compaction paths "
                       "(the tiered3 run tier bounds them)",
        "capacity": capacity,
        "max_batch_len": max_len,
        "max_emit": 1,
        "batches_timed": max_batches,
        "repeats": repeats,
        "occupancy_fraction": int(capacity * occupancy) / capacity,
        "per_batch_us": per_batch,
        "per_batch_samples_us": samples,
        "low_occupancy_us": low,
        "low_occupancy_fraction": 0.25,
        "tiered_pressure_ratio_vs_low_occupancy":
            per_batch["tiered"] / low["tiered"] if low else None,
        "tiered3_pressure_ratio_vs_low_occupancy":
            per_batch["tiered3"] / low["tiered3"] if low else None,
        "capacity_sweep": {
            "occupancy_fraction": occupancy,
            "capacities": rows,
            "tiered3_ratio_64k_over_1k": ratio("tiered3", 65536, 1024),
            "tiered_ratio_64k_over_1k": ratio("tiered", 65536, 1024),
        } if sweep else None,
    }


def validate_overhead(quick: bool = False, repeats: int = 5):
    """Cost of the on-device invariant auditor: ``validate='cheap'``
    (O(front) fault bits folded into the while-loop carry every
    super-step) vs ``validate='off'`` on the IDENTICAL churn workload.

    The two engines run in interleaved rounds, so the recorded
    ``cheap_over_off`` ratio is host-drift-free — that ratio is the
    CI-gated quantity (``--check-validate``): the auditor's contract is
    "always-on-able", i.e. a small constant factor, not a new scaling
    term.
    """
    max_len = 16
    capacity = 1024 if quick else 4096
    max_batches = 128 if quick else 512

    # An HONEST variant of the churn model: same near/far re-emit
    # shape, but the declared lookahead (17) really bounds every emit
    # delay.  (_churn_registry declares 1e6 while emitting at t+17 — a
    # fine perf stressor, but the clock-regression bit would correctly
    # flag it, so it cannot A/B the validator.)
    def _honest_churn():
        reg = EventRegistry()

        @emits_events
        def churn(state, t, arg):
            far = jnp.floor(t / 16.0) % 2.0 == 0.0
            delay = jnp.where(far, jnp.float32(1e6), jnp.float32(17.0))
            emit = jnp.zeros((1, 2 + ARG_WIDTH), jnp.float32)
            emit = emit.at[0, 0].set(t + delay).at[0, 1].set(0.0)
            return state + 1, emit

        reg.register("Churn", churn, lookahead=17.0)
        return reg.freeze()

    def engine(validate):
        return DeviceEngine(_honest_churn(),
                            max_batch_len=max_len, capacity=capacity,
                            max_emit=1, queue_mode="tiered3",
                            validate=validate)

    events = [(float(t), 0, None) for t in range(capacity // 2)]
    timed = _time_engines_interleaved(
        {"off": (engine("off"), events),
         "cheap": (engine("cheap"), events)},
        max_batches, repeats)
    # The gated ratio uses min-of-samples, not the median: host noise
    # on a shared box only ever ADDS time, so each side's minimum is
    # its best floor estimate, and the min/min ratio tracks the actual
    # kernel-count overhead instead of whichever round caught a noise
    # spike (the raw samples are kept alongside for re-judging).
    per_batch = {m: float(np.min(t[1])) for m, t in timed.items()}
    return {
        "description": "validate='cheap' per-super-step fault bits vs "
                       "validate='off', identical tiered3 churn workload "
                       "in interleaved rounds (min-of-samples ratio is "
                       "the gated value)",
        "capacity": capacity,
        "max_batch_len": max_len,
        "batches_timed": max_batches,
        "repeats": repeats,
        "per_batch_us": per_batch,
        "per_batch_samples_us": {m: t[1] for m, t in timed.items()},
        "cheap_over_off": per_batch["cheap"] / per_batch["off"],
    }


def _print_validate(vo):
    pb = vo["per_batch_us"]
    print(f"validate overhead @ cap={vo['capacity']}: "
          f"off={pb['off']:.1f}us/batch cheap={pb['cheap']:.1f}us/batch "
          f"(cheap/off {vo['cheap_over_off']:.3f}x)")


def _merge_validate_into_json(vo):
    _merge_into_json({"validate_overhead": vo})


def _check_validate_overhead(vo, max_ratio: float) -> int:
    """CI gate: the cheap auditor must stay within ``max_ratio``x of
    validate='off' on the same box (an absolute ceiling — both sides
    of the ratio are measured fresh in the same interleaved rounds, so
    there is no recorded baseline to drift against).  Returns a process
    exit code."""
    fresh = vo["cheap_over_off"]
    print(f"validate gate: cheap/off {fresh:.3f}x (ceiling "
          f"{max_ratio:.2f}x)")
    if fresh > max_ratio:
        print(f"validate gate: FAIL — cheap validation costs "
              f"{fresh:.3f}x, above the {max_ratio:.2f}x ceiling")
        return 1
    print("validate gate: OK")
    return 0


class _DecodeBoundSource:
    """Arrival-source wrapper that sleeps per block, emulating a trace
    whose blocks cost real host time to produce (disk decode, feature
    hydration).  Sleeping — not spinning — so the hidden work truly
    overlaps the device segment instead of stealing its CPU."""

    def __init__(self, inner, delay_s: float):
        self.inner = inner
        self.delay_s = delay_s
        self.block_size = inner.block_size

    def __len__(self):
        return len(self.inner)

    def seek(self, cursor: int) -> None:
        self.inner.seek(cursor)

    def blocks(self):
        for block in self.inner.blocks():
            time.sleep(self.delay_s)
            yield block


def _stream_bit_equal(streamed, closed) -> bool:
    if streamed.events != closed.events or \
            streamed.dropped != closed.dropped or \
            np.float32(streamed.final_time) != np.float32(closed.final_time):
        return False
    return all(
        np.array_equal(np.asarray(streamed.state[k]), np.asarray(v))
        for k, v in closed.state.items())


def streaming(quick: bool = False, repeats: int = 5,
              trace: str | None = None):
    """Open-system ingestion (DESIGN.md §10): sustained host→device
    arrival throughput on the serving admission scenario.

    Four measurements on the SAME Poisson trace, interleaved rounds:

    - ``preseeded`` — the closed reference: the whole trace pushed into
      the queue up front.  The wall-time denominator of the gated
      ``streamed_over_preseeded`` ratio (both sides fresh each run, so
      the gate is an absolute overhead ceiling, machine-independent).
    - ``streamed`` — ``run(arrivals=...)`` with the double-buffered
      prefetch feeder; ``streaming_rps`` = requests / wall is the
      recorded serving axis.
    - ``sync_feed`` — the same run with ``_stream_prefetch=False``
      (block built + staged inline at each segment boundary).
    - ``decode_bound`` — both feed modes again on a source that sleeps
      per block (~half the streamed wall in total): the recorded
      ``sync_over_prefetch`` shows the double buffer actually hiding
      host block cost behind device segments, which the cheap synthetic
      source is too fast to expose.

    A bounded-memory variant (device queue ~1/4 the trace length,
    ``overflow='spill'``) re-runs the streamed side and is bit-compared
    against the SAME closed reference — the serving shape where the
    backlog never fits on device.  With ``trace=`` (``--trace``), a
    trace file from ``scripts/gen_trace.py`` replays through the
    bounded config at scale (the >=1M-request acceptance run) and its
    ``streaming_rps`` + bit-equality land in a ``trace_replay``
    subsection; sized so the closed reference still fits in one queue.
    """
    from repro.core.program import Config
    from repro.serving.scenarios import build_open_admission_program
    from repro.serving.scenarios import initial_state as admission_state
    from repro.stream import PoissonSource, TraceReader, source_events

    # slots sized so service (~slots / 3.5 ticks mean decode) outruns
    # the arrival rate — an underprovisioned admission system melts
    # into an ADMIT retry storm, which stresses the queue, not the
    # ingestion path this section measures.  max_batch_len stays at 3
    # like every serving workload here: scenario compile time grows
    # steeply with lane count (~10s at 3, minutes at 5+).
    n_req = 1_500 if quick else 8_000
    num_slots = 64
    max_len = 3
    src = PoissonSource(16.0, n_req, seed=11, grid=0.25, type_id=0,
                        block_size=256)
    bounded_cap = max(512, n_req // 4)

    def build(capacity, n=n_req, slots=num_slots, mbl=max_len):
        return build_open_admission_program(
            num_slots=slots, num_requests=n, max_decode=6,
            config=Config(max_batch_len=mbl, capacity=capacity,
                          max_emit=2))

    state0 = admission_state(num_slots)
    events = [(1.0, "TICK")] + [
        (t, ty, list(a)) for (t, ty, a) in source_events(src)]
    sim_closed = build(n_req + 2048).build(backend="device")
    sim_open = build(n_req + 2048).build(backend="device")
    sim_bounded = build(bounded_cap).build(backend="device",
                                           overflow="spill")

    # warm every jit cache once
    closed = sim_closed.run(state0, events=events)
    src.seek(0)
    streamed = sim_open.run(state0, arrivals=src)
    src.seek(0)
    bounded = sim_bounded.run(state0, arrivals=src)
    # a post-warm streamed wall sizes the decode-bound sleep (total
    # sleep ~= half the streamed wall — sizing off the FIRST run would
    # fold jit compile into the delay and swamp the segments it is
    # supposed to hide behind)
    src.seek(0)
    t0 = time.perf_counter()
    streamed = sim_open.run(state0, arrivals=src)
    warm_wall = time.perf_counter() - t0
    bit = _stream_bit_equal(streamed, closed) and \
        _stream_bit_equal(bounded, closed)
    assert streamed.ingested == n_req and bounded.ingested == n_req
    n_blocks = -(-n_req // src.block_size)
    delay_s = 0.5 * warm_wall / n_blocks
    slow = _DecodeBoundSource(src, delay_s)

    def timed_closed():
        t = time.perf_counter()
        sim_closed.run(state0, events=events)
        return time.perf_counter() - t

    def timed_stream(sim, source, **kw):
        source.seek(0)
        t = time.perf_counter()
        sim.run(state0, arrivals=source, **kw)
        return time.perf_counter() - t

    rounds = {
        "preseeded": timed_closed,
        "streamed": lambda: timed_stream(sim_open, src),
        "sync_feed": lambda: timed_stream(sim_open, src,
                                          _stream_prefetch=False),
        "decode_bound_prefetch": lambda: timed_stream(sim_open, slow),
        "decode_bound_sync": lambda: timed_stream(
            sim_open, slow, _stream_prefetch=False),
        "bounded_spill": lambda: timed_stream(sim_bounded, src),
    }
    samples = {m: [] for m in rounds}
    for _ in range(repeats):
        for m, fn in rounds.items():
            samples[m].append(fn())
    med = {m: float(np.median(s)) for m, s in samples.items()}
    best = {m: float(np.min(s)) for m, s in samples.items()}
    return {
        "description": "open-system ingestion on the serving admission "
                       "scenario: streamed run(arrivals=...) vs the "
                       "pre-seeded closed reference, interleaved "
                       "rounds; streaming_rps = requests / median "
                       "streamed wall; the gated streamed_over_"
                       "preseeded ratio uses min-of-samples",
        "n_requests": n_req,
        "num_slots": num_slots,
        "max_batch_len": max_len,
        "events": int(closed.events),
        "bounded_capacity": bounded_cap,
        "repeats": repeats,
        "wall_s": med,
        "wall_samples_s": samples,
        "streaming_rps": n_req / med["streamed"],
        "bounded_streaming_rps": n_req / med["bounded_spill"],
        "streamed_over_preseeded": best["streamed"] / best["preseeded"],
        "decode_bound": {
            "delay_per_block_s": delay_s,
            "blocks": n_blocks,
            "sync_over_prefetch": best["decode_bound_sync"]
            / best["decode_bound_prefetch"],
        },
        "bit_identical": bool(bit),
        **({"trace_replay": _trace_replay(trace, build, admission_state,
                                          TraceReader, source_events)}
           if trace is not None else {}),
    }


def _trace_replay(trace, build, admission_state, TraceReader,
                  source_events):
    """The acceptance-scale run: replay an on-disk trace through the
    bounded-memory streamed config and bit-compare against the closed
    pre-seeded reference.  One shot each — at >=1M requests the walls
    are seconds-to-minutes and the quantity of interest is sustained
    RPS, not a noise-grade median."""
    reader = TraceReader(trace)
    n = len(reader)
    slots = 1024
    mbl = 3
    state0 = admission_state(slots)
    sim_b = build(32_768, n=n, slots=slots,
                  mbl=mbl).build(backend="device", overflow="spill")
    res = sim_b.run(state0, arrivals=reader)
    reader.seek(0)
    t0 = time.perf_counter()
    res = sim_b.run(state0, arrivals=reader)
    wall = time.perf_counter() - t0
    assert res.ingested == n, (res.ingested, n)

    events = [(1.0, "TICK")] + [
        (t, ty, list(a)) for (t, ty, a) in source_events(reader)]
    sim_c = build(n + 4096, n=n, slots=slots,
                  mbl=mbl).build(backend="device")
    t0 = time.perf_counter()
    closed = sim_c.run(state0, events=events)
    closed_wall = time.perf_counter() - t0
    return {
        "trace": str(trace),
        "n_requests": n,
        "num_slots": slots,
        "max_batch_len": mbl,
        "bounded_capacity": 32_768,
        "events": int(closed.events),
        "streamed_wall_s": wall,
        "preseeded_wall_s": closed_wall,
        "streaming_rps": n / wall,
        "bit_identical": _stream_bit_equal(res, closed),
    }


def _print_streaming(st):
    w = st["wall_s"]
    print(f"streaming @ n={st['n_requests']}: "
          f"{st['streaming_rps']:,.0f} RPS sustained "
          f"(bounded cap={st['bounded_capacity']}: "
          f"{st['bounded_streaming_rps']:,.0f} RPS); "
          f"streamed/preseeded {st['streamed_over_preseeded']:.3f}x "
          f"(walls {w['streamed'] * 1e3:.0f}ms / "
          f"{w['preseeded'] * 1e3:.0f}ms)")
    db = st["decode_bound"]
    print(f"  decode-bound source ({db['delay_per_block_s'] * 1e3:.1f}"
          f"ms x {db['blocks']} blocks): sync/prefetch "
          f"{db['sync_over_prefetch']:.3f}x (double-buffer overlap)")
    print(f"  streamed == preseeded bit-identical: "
          f"{st['bit_identical']}")
    tr = st.get("trace_replay")
    if tr:
        print(f"  trace replay {tr['trace']}: n={tr['n_requests']:,} "
              f"{tr['streaming_rps']:,.0f} RPS "
              f"(wall {tr['streamed_wall_s']:.1f}s, closed ref "
              f"{tr['preseeded_wall_s']:.1f}s), bit_identical="
              f"{tr['bit_identical']}")


def _merge_streaming_into_json(st):
    # deep merge keeps a recorded acceptance-scale trace_replay when a
    # quick/CI refresh runs without --trace
    _merge_into_json({"streaming": st})


def _check_streaming(st, max_ratio: float) -> int:
    """CI gate: streamed execution must stay bit-identical to the
    pre-seeded closed reference AND within ``max_ratio``x of its wall
    time (both sides fresh in the same interleaved rounds — an
    absolute ceiling, nothing recorded to drift against).  The
    decode-bound overlap is printed, not gated: it quantifies the
    double buffer but is scheduler-noise-sensitive on shared runners.
    Returns a process exit code."""
    fresh = st["streamed_over_preseeded"]
    print(f"streaming gate: bit_identical={st['bit_identical']} "
          f"streamed/preseeded {fresh:.3f}x (ceiling {max_ratio:.2f}x)")
    if not st["bit_identical"]:
        print("streaming gate: FAIL — streamed run diverged from the "
              "pre-seeded closed reference")
        return 1
    if fresh > max_ratio:
        print(f"streaming gate: FAIL — streamed ingestion costs "
              f"{fresh:.3f}x the pre-seeded run, above the "
              f"{max_ratio:.2f}x ceiling")
        return 1
    print("streaming gate: OK")
    return 0


def _routed_churn_registry(near_delay: float, num_entities: int):
    """The near-full churn shape WITH entity routing: each re-emit
    targets the next entity (mod ``num_entities``), so under the
    sharded engine a constant fraction of emissions cross shard
    boundaries and exercise the exchange merge, while the single-queue
    engines see the identical event stream (they ignore ``arg[0]``)."""
    reg = EventRegistry()

    @emits_events
    def churn(state, t, arg):
        far = jnp.floor(t / 16.0) % 2.0 == 0.0
        delay = jnp.where(far, jnp.float32(1e6), jnp.float32(near_delay))
        emit = jnp.zeros((1, 2 + ARG_WIDTH), jnp.float32)
        emit = emit.at[0, 0].set(t + delay).at[0, 1].set(0.0)
        emit = emit.at[0, 2].set(
            jnp.mod(arg[0] + 1.0, float(num_entities)))
        return state + 1, emit

    reg.register("Churn", churn, lookahead=1e6)
    return reg.freeze()


def shards_sweep(quick: bool = False, repeats: int = 5):
    """`--shards`: the sharded engine vs the single tiered3 queue.

    The 92%-occupancy routed churn (near-head/far-future re-emits, one
    event per entity hop) runs on shards ∈ {1, 2, 4} at each capacity
    — shards=1 is the plain ``DeviceEngine(queue_mode="tiered3")``
    baseline the sharded runs are bit-identical to.  Interleaved A/B
    rounds (``_time_engines_interleaved``), so host-load drift hits
    every engine equally.  What this records is the COST of the
    lookahead-synchronized merge/exchange machinery per super-step
    (each super-step executes exactly the single-queue window, so
    per-batch numbers are directly comparable); per-shard queue work
    stays bounded, so the overhead ratio should stay flat in capacity.
    """
    import os

    import jax

    max_len = 16
    num_entities = 64
    max_batches = 128 if quick else 512
    occupancy = 0.92
    caps = [1024] if quick else [4096, 65536]
    shard_counts = (1, 2, 4)
    # The placement axis (DESIGN.md §12): the shard_map'd devices
    # placement rides the sweep whenever the process sees enough
    # devices (CI forces 4 host devices).  Recorded next to the host
    # core count — forced host devices on a starved core budget
    # timeshare, so the devices row is only a real speedup when
    # cores >= shards.
    devices_counts = tuple(
        n for n in shard_counts
        if n > 1 and len(jax.devices()) >= n
    )

    def engine(n_shards, cap, placement="serial"):
        reg = _routed_churn_registry(17.0, num_entities)
        kw = dict(max_batch_len=max_len, capacity=cap, max_emit=1)
        if n_shards == 1:
            return DeviceEngine(reg, queue_mode="tiered3", **kw)
        return ShardedDeviceEngine(reg, shards=n_shards,
                                   placement=placement, **kw)

    def seeded(cap):
        return [(float(t), 0,
                 np.asarray([t % num_entities, 0, 0, 0], np.float32))
                for t in range(int(cap * occupancy))]

    rows = {}
    for cap in caps:
        contenders = {f"shards={n}": (engine(n, cap), seeded(cap))
                      for n in shard_counts}
        for n in devices_counts:
            contenders[f"shards={n}/devices"] = (
                engine(n, cap, "devices"), seeded(cap))
        timed = _time_engines_interleaved(contenders, max_batches,
                                          repeats)
        rows[str(cap)] = {
            label: {"per_batch_us": t[0], "per_batch_samples_us": t[1]}
            for label, t in timed.items()
        }

    def ratio(cap, num_label, den_label):
        row = rows.get(str(cap))
        if not row or num_label not in row or den_label not in row:
            return None
        return (row[num_label]["per_batch_us"]
                / row[den_label]["per_batch_us"])

    big = caps[-1]
    out = {
        "description": "routed near-full churn (92% occupancy, "
                       "cross-entity re-emits); sharded engine vs the "
                       "bit-identical single tiered3 queue, interleaved "
                       "rounds; */devices rows run the same engine "
                       "under placement='devices' (one device per "
                       "shard)",
        "max_batch_len": max_len,
        "max_emit": 1,
        "num_entities": num_entities,
        "batches_timed": max_batches,
        "repeats": repeats,
        "occupancy_fraction": occupancy,
        "host_cpu_count": os.cpu_count(),
        "visible_devices": len(jax.devices()),
        "device_platform": jax.devices()[0].platform,
        "capacities": rows,
        f"shards2_over_single_at_{big}": ratio(big, "shards=2",
                                               "shards=1"),
        f"shards4_over_single_at_{big}": ratio(big, "shards=4",
                                               "shards=1"),
    }
    for n in devices_counts:
        out[f"devices{n}_over_serial{n}_at_{big}"] = ratio(
            big, f"shards={n}/devices", f"shards={n}")
    return out


def _check_shards(sweep, max_ratio: float) -> int:
    """CI gate (``--check-shards``): the fresh devices/serial ratio at
    the widest shard count must not exceed ``max_ratio`` — the
    shard_map placement must never silently regress against its own
    serial spec.  Fails loudly if the sweep never saw enough devices
    to record the placement axis."""
    keys = sorted(k for k in sweep
                  if k.startswith("devices") and "_over_serial" in k)
    if not keys:
        print("shards gate: no devices/serial ratio measured (the "
              "sweep saw too few devices; set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
        return 1
    key = keys[-1]
    ratio = sweep[key]
    if ratio is None or ratio > max_ratio:
        print(f"shards gate: FAIL {key} = {ratio} "
              f"(ceiling {max_ratio:.2f}x)")
        return 1
    print(f"shards gate: OK {key} = {ratio:.3f} "
          f"(ceiling {max_ratio:.2f}x)")
    return 0


def _fused_workload_builders(quick: bool):
    """label -> (build(**kw) -> CompiledSim, state0_fn) for the two
    fused-dispatch workloads: the PoC model (2 types, the paper's
    motivating example) and the serving admission scenario (5 types —
    a word space where the default hot set really is a subset)."""
    from repro.core.program import Config
    from repro.serving.scenarios import build_admission_program
    from repro.serving.scenarios import initial_state as admission_state

    num_events = 192 if quick else 768
    rng = np.random.default_rng(0)
    types = (rng.random(num_events) < 0.5).astype(int)

    def build_poc(**kw):
        # p_set = 0.5 and max_batch_len = 6: most windows contain a
        # Set, and in a straight-line branch (switch/fused) everything
        # before the last Set is dead code and everything after it
        # runs on a compile-time constant — the paper's §I motivating
        # optimization.  The masked per-lane path executes every
        # Increment loop live, so the hot-word comparison measures
        # exactly the cross-event scope fused dispatch preserves.
        prog = poc.build_program(
            iters=32,
            config=Config(max_batch_len=6, capacity=num_events + 8),
        )
        for t, ty in enumerate(types):
            prog.schedule(float(t), ("Increment", "Set")[int(ty)])
        # declared for hot_words="static" (fused_dispatch_static)
        prog.example_state(poc.initial_state())
        return prog.build(backend="device", **kw)

    num_requests = 24 if quick else 96

    def build_serving(**kw):
        prog = build_admission_program(
            num_slots=8, num_requests=num_requests, max_decode=5,
            config=Config(max_batch_len=3, capacity=1024, max_emit=2),
        )
        prog.example_state(admission_state(8))
        return prog.build(backend="device", **kw)

    return {
        "poc": (build_poc, poc.initial_state),
        "serving": (build_serving, lambda: admission_state(8)),
    }


def _time_sims_interleaved(sims, state0_fn, repeats):
    """The `_time_engines_interleaved` protocol at the CompiledSim
    level (dict states, re-runnable handles): label -> (median µs per
    batch, samples)."""
    for sim in sims.values():
        for _ in range(2):  # compile + allocator warm-up
            jax.block_until_ready(sim.run(state0_fn()).state)
    samples = {label: [] for label in sims}
    for _ in range(max(1, repeats)):
        for label, sim in sims.items():
            s0 = state0_fn()
            t0 = time.perf_counter()
            r = sim.run(s0)
            jax.block_until_ready(r.state)
            samples[label].append(
                (time.perf_counter() - t0) / r.batches * 1e6)
    return {label: (float(np.median(v)), v)
            for label, v in samples.items()}


def fused_dispatch(quick: bool = False, repeats: int = 5):
    """Composition-specialized dispatch vs the masked and full-switch
    paths — whole-run and per-dispatch (see module docstring)."""
    from repro.core.composer import hot_words_from_counts

    out = {}
    for wl, (build, state0_fn) in _fused_workload_builders(quick).items():
        sims = {mode: build(dispatch_mode=mode)
                for mode in ("switch", "masked")}

        # Profile pass on the generic modes, then specialize: the
        # fused sim gets the top-W PROFILED words (the intended
        # profile -> hot_words workflow), not the default dense-code
        # prefix — the observed hot words need not be the short ones.
        profiles = {m: sims[m].run(state0_fn()) for m in sims}
        base = profiles["switch"]
        hot = hot_words_from_counts(base.word_counts,
                                    sims["switch"].engine.codec, 8)
        sims["fused"] = build(dispatch_mode="fused", hot_words=hot)
        profiles["fused"] = sims["fused"].run(state0_fn())
        for m, r in profiles.items():
            np.testing.assert_array_equal(r.word_counts,
                                          base.word_counts, err_msg=m)
        hot_code = int(np.argmax(base.word_counts))

        timed = _time_sims_interleaved(sims, state0_fn, repeats)
        per_batch = {m: t[0] for m, t in timed.items()}

        # Per-dispatch microbenchmark on the hottest word, chained on
        # the state (the same _bench_op_loop shape as the per-op split).
        eng = sims["switch"].engine
        word = tuple(eng.codec.decode(hot_code))
        k = eng.max_batch_len
        tys_np = np.zeros((k,), np.int32)
        tys_np[: len(word)] = word
        ts = jnp.asarray(np.arange(k, dtype=np.float32))
        tys = jnp.asarray(tys_np)
        args = jnp.zeros((k, ARG_WIDTH), jnp.float32)
        length = jnp.int32(len(word))
        code = jnp.int32(hot_code)
        s0 = state0_fn()
        eng_f = sims["fused"].engine
        eng_m = sims["masked"].engine
        # The window rides in the loop carry: closed-over arrays embed
        # as jaxpr constants, XLA folds the dispatch switch on a
        # constant index, and the "dispatch" loop would time only the
        # branch body.
        def _carried(fn):
            def step(c):
                s, code, ts, tys, args, length = c
                return ((fn(s, code, ts, tys, args, length),)
                        + c[1:])
            return step

        op_us = _bench_ops_interleaved({
            "switch": _carried(
                lambda s, c, ts, tys, args, n:
                eng.dispatch(c, s, ts, tys, args)[0]),
            "masked": _carried(
                lambda s, c, ts, tys, args, n:
                eng_m.dispatch(s, ts, tys, args, n)[0]),
            "fused": _carried(
                lambda s, c, ts, tys, args, n:
                eng_f.dispatch(c, s, ts, tys, args, n)[0]),
        }, (s0, code, ts, tys, args, length), 256)

        out[wl] = {
            "batches": base.batches,
            "events": base.events,
            "hot_word": list(word),
            "hot_word_share": float(
                base.word_counts[hot_code] / base.word_counts.sum()),
            "num_hot_words": eng_f.dispatch.num_hot,
            "num_batch_words": eng.codec.num_batches,
            "repeats": repeats,
            "per_batch_us": per_batch,
            "per_batch_samples_us": {m: t[1] for m, t in timed.items()},
            "run_fused_over_masked":
                per_batch["fused"] / per_batch["masked"],
            "dispatch_op_us": op_us,
            "dispatch_fused_over_masked": op_us["fused"] / op_us["masked"],
        }
    return {
        "description": "dispatch modes on identical workloads: full "
                       "switch over all words / generic per-lane masked "
                       "path / top-W fused super-procedures with masked "
                       "fallback; dispatch_op_us times the hottest "
                       "profiled word per dispatch call",
        "workloads": out,
    }


def fused_dispatch_static(quick: bool = False, repeats: int = 5):
    """``hot_words="static"`` vs the profiled hot set on the fused
    workloads: the analyzer-chosen compile-time hot set costs no
    profiling run, and this section records what it costs per batch
    instead.  Results are asserted bit-identical across the two hot
    sets (hot-set choice is a pure specialization decision), and
    ``hot_share`` records the fraction of executed batches each hot
    set actually covers (the rest take the masked fallback)."""
    from repro.core.composer import hot_words_from_counts

    out = {}
    for wl, (build, state0_fn) in _fused_workload_builders(quick).items():
        base = build(dispatch_mode="switch")
        profile = base.run(state0_fn())
        hot = hot_words_from_counts(profile.word_counts,
                                    base.engine.codec, 8)
        sims = {
            "profiled": build(dispatch_mode="fused", hot_words=hot),
            "static": build(dispatch_mode="fused", hot_words="static"),
        }
        results = {m: s.run(state0_fn()) for m, s in sims.items()}
        for m, r in results.items():
            np.testing.assert_array_equal(
                r.word_counts, profile.word_counts, err_msg=m)
            assert r.events == profile.events, m

        codec = base.engine.codec
        counts = np.asarray(profile.word_counts, np.float64)

        def share(words):
            codes = [codec.encode(list(w)) for w in words]
            return float(counts[codes].sum() / counts.sum())

        timed = _time_sims_interleaved(sims, state0_fn, repeats)
        per_batch = {m: t[0] for m, t in timed.items()}
        out[wl] = {
            "batches": profile.batches,
            "events": profile.events,
            "num_hot_words": sims["static"].engine.dispatch.num_hot,
            "static_hot_words": [list(w)
                                 for w in sims["static"].engine.hot_words],
            "hot_share": {m: share(s.engine.hot_words)
                          for m, s in sims.items()},
            "repeats": repeats,
            "per_batch_us": per_batch,
            "per_batch_samples_us": {m: t[1] for m, t in timed.items()},
            "run_static_over_profiled":
                per_batch["static"] / per_batch["profiled"],
        }
    return {
        "description": "fused dispatch with the analyzer's compile-time "
                       "hot set (hot_words='static', DESIGN.md §11) vs "
                       "the top-W profiled words on identical workloads; "
                       "results are bit-identical, hot_share is the "
                       "fraction of batches each hot set covers",
        "workloads": out,
    }


def _print_fused(fd):
    for wl, row in fd["workloads"].items():
        pb = row["per_batch_us"]
        op = row["dispatch_op_us"]
        print(f"  fused dispatch [{wl}] hot={row['hot_word']} "
              f"({row['num_hot_words']}/{row['num_batch_words']} words "
              f"hot): per-batch switch={pb['switch']:.1f}us "
              f"masked={pb['masked']:.1f}us fused={pb['fused']:.1f}us | "
              f"per-dispatch switch={op['switch']:.2f}us "
              f"masked={op['masked']:.2f}us fused={op['fused']:.2f}us "
              f"(fused/masked {row['dispatch_fused_over_masked']:.2f}x)")


def _print_fused_static(fds):
    for wl, row in fds["workloads"].items():
        pb = row["per_batch_us"]
        hs = row["hot_share"]
        print(f"  static hot words [{wl}] "
              f"({row['num_hot_words']} words hot): per-batch "
              f"profiled={pb['profiled']:.1f}us "
              f"static={pb['static']:.1f}us "
              f"(static/profiled {row['run_static_over_profiled']:.2f}x) "
              f"| hot-set coverage profiled={hs['profiled']:.0%} "
              f"static={hs['static']:.0%}")


def _merge_fused_into_json(fd, fds=None):
    fresh = {"fused_dispatch": fd}
    if fds is not None:
        fresh["fused_dispatch_static"] = fds
    _merge_into_json(fresh)


def _check_fused_baseline(fd, max_ratio: float) -> int:
    """CI perf gate for the dispatch specialization: per workload, the
    fused/masked per-dispatch ratio — host speed cancels, a fused-path
    regression does not — must stay within ``max_ratio``× the recorded
    ratio.  Returns a process exit code."""
    if not JSON_PATH.exists():
        print(f"baseline check: no {JSON_PATH.name}; nothing to "
              f"compare ({_REGEN_FUSED})")
        return 1
    base = json.loads(JSON_PATH.read_text()).get("fused_dispatch")
    if not base:
        print(f"baseline check: {JSON_PATH.name} has no "
              f"'fused_dispatch' key ({_REGEN_FUSED})")
        return 1
    code = 0
    for wl, row in fd["workloads"].items():
        rec = base.get("workloads", {}).get(wl)
        if not rec:
            print(f"baseline check [{wl}]: not in recorded baseline; "
                  "skipping")
            continue
        recorded = rec.get("dispatch_fused_over_masked")
        if recorded is None:
            # A hand-edited or pre-dispatch-gate baseline: fail with
            # instructions instead of a bare KeyError traceback.
            print(f"baseline check [{wl}]: recorded 'fused_dispatch' "
                  "entry lacks 'dispatch_fused_over_masked' — stale "
                  f"baseline format ({_REGEN_FUSED})")
            code = 1
            continue
        fresh = row["dispatch_fused_over_masked"]
        limit = recorded * max_ratio
        print(f"baseline check [{wl}]: fresh fused/masked {fresh:.2f}x "
              f"vs recorded {recorded:.2f}x (limit {limit:.2f}x)")
        if fresh > limit:
            print(f"baseline check [{wl}]: FAIL — fused dispatch "
                  f"regressed {fresh / recorded:.2f}x vs baseline")
            code = 1
    if code == 0:
        print("baseline check: OK")
    return code


def _check_fused_static_baseline(fds, max_ratio: float) -> int:
    """Gate for the compile-time hot set: per workload, the
    static/profiled per-batch ratio must stay within ``max_ratio``×
    the recorded ratio — a static hot-set selection that stops
    matching what runs shows up here as fallback-path batches."""
    if not JSON_PATH.exists():
        print(f"static baseline check: no {JSON_PATH.name}; nothing "
              f"to compare ({_REGEN_FUSED})")
        return 1
    base = json.loads(JSON_PATH.read_text()).get("fused_dispatch_static")
    if not base:
        print(f"static baseline check: {JSON_PATH.name} has no "
              f"'fused_dispatch_static' key ({_REGEN_FUSED})")
        return 1
    code = 0
    for wl, row in fds["workloads"].items():
        rec = base.get("workloads", {}).get(wl)
        if not rec:
            print(f"static baseline check [{wl}]: not in recorded "
                  "baseline; skipping")
            continue
        recorded = rec.get("run_static_over_profiled")
        if recorded is None:
            print(f"static baseline check [{wl}]: recorded "
                  "'fused_dispatch_static' entry lacks "
                  f"'run_static_over_profiled' ({_REGEN_FUSED})")
            code = 1
            continue
        fresh = row["run_static_over_profiled"]
        limit = recorded * max_ratio
        print(f"static baseline check [{wl}]: fresh static/profiled "
              f"{fresh:.2f}x vs recorded {recorded:.2f}x "
              f"(limit {limit:.2f}x)")
        if fresh > limit:
            print(f"static baseline check [{wl}]: FAIL — static hot "
                  f"set regressed {fresh / recorded:.2f}x vs baseline")
            code = 1
    if code == 0:
        print("static baseline check: OK")
    return code


def _print_shards(sh):
    for cap, row in sh["capacities"].items():
        parts = " ".join(
            f"{label}={vals['per_batch_us']:.1f}us"
            for label, vals in row.items())
        print(f"  shards sweep cap={cap:>6}: {parts}")
    for key, val in sh.items():
        if "_over_" in key and val is not None:
            print(f"  shards sweep {key}: {val:.3f}")


def _merge_shards_into_json(sh):
    _merge_into_json({"scheduling_overhead": {"shards_sweep": sh}})


def _merge_near_full_into_json(nf):
    """Refresh only the near_full section, keeping the recorded
    anchor/sweep baselines intact."""
    _merge_into_json({"scheduling_overhead": {"near_full": nf}})


def _print_near_full(nf):
    pb = nf["per_batch_us"]
    line = (f"near-full (occupancy {nf['occupancy_fraction']:.0%}, "
            f"cap={nf['capacity']}, median of {nf['repeats']}): "
            f"tiered3={pb['tiered3']:.1f}us/batch "
            f"tiered={pb['tiered']:.1f}us/batch "
            f"flat={pb['flat']:.1f}us/batch")
    if nf.get("low_occupancy_us"):
        line += (f" | at {nf['low_occupancy_fraction']:.0%} occupancy: "
                 f"tiered3={nf['low_occupancy_us']['tiered3']:.1f}us "
                 f"(pressure ratio "
                 f"{nf['tiered3_pressure_ratio_vs_low_occupancy']:.2f}x; "
                 f"two-tier "
                 f"{nf['tiered_pressure_ratio_vs_low_occupancy']:.2f}x)")
    print(line)
    if not nf.get("capacity_sweep"):
        return
    for cap, row in nf["capacity_sweep"]["capacities"].items():
        print(f"  near-full cap={cap:>6}: "
              f"tiered3={row['tiered3']['per_batch_us']:.1f}us "
              f"tiered={row['tiered']['per_batch_us']:.1f}us")
    r3 = nf["capacity_sweep"]["tiered3_ratio_64k_over_1k"]
    r2 = nf["capacity_sweep"]["tiered_ratio_64k_over_1k"]
    if r3 is not None:
        print(f"  worst-case capacity scaling 64k/1k: tiered3 {r3:.2f}x "
              f"vs two-tier {r2:.2f}x")


def _check_near_full_baseline(nf, max_ratio: float) -> int:
    """CI perf gate: fail when tiered3's near-full cost regresses more
    than ``max_ratio``× the recorded baseline.

    Absolute microseconds do not transfer between the recording
    machine and a CI runner (DESIGN.md §6.4), so the gated quantity is
    the tiered3/flat per-batch RATIO — both sides measured in the same
    interleaved rounds, so host speed cancels while a tiered3-specific
    regression does not.  Falls back to the absolute tiered3 (or
    pre-tiered3 two-tier) median only when the recorded baseline
    predates the flat column.  Returns a process exit code.
    """
    if not JSON_PATH.exists():
        print(f"baseline check: no {JSON_PATH.name}; nothing to compare")
        return 1
    payload = json.loads(JSON_PATH.read_text())
    base = payload.get("scheduling_overhead", {}).get("near_full")
    if not base:
        print("baseline check: no recorded near_full section")
        return 1
    base_pb = base.get("per_batch_us")
    if not base_pb or not ("tiered3" in base_pb or "tiered" in base_pb):
        # Guard against a hand-edited / truncated baseline file: the
        # gate should say what to re-record, not dump a KeyError.
        print("baseline check: recorded near_full section lacks "
              "'per_batch_us' medians — stale or truncated baseline; "
              "re-record with --near-full-only (no --quick)")
        return 1
    fresh_pb = nf["per_batch_us"]
    if "tiered3" in base_pb and "flat" in base_pb:
        recorded = base_pb["tiered3"] / base_pb["flat"]
        fresh = fresh_pb["tiered3"] / fresh_pb["flat"]
        what = "tiered3/flat per-batch ratio"
        units = "x"
    else:
        recorded = base_pb.get("tiered3", base_pb.get("tiered"))
        fresh = fresh_pb["tiered3"]
        what = "tiered3 per-batch (absolute — old baseline, machine-"
        what += "dependent)"
        units = "us"
    if base.get("capacity") != nf["capacity"]:
        # Neither comparison transfers across capacities: flat's cost
        # is O(capacity), so the tiered3/flat ratio shifts with it.
        print(f"baseline check: FAIL — recorded baseline is at capacity "
              f"{base.get('capacity')}, this run at {nf['capacity']}; "
              "run the gate at the recorded capacity (no --quick)")
        return 1
    limit = recorded * max_ratio
    print(f"baseline check: fresh {what} {fresh:.2f}{units} vs recorded "
          f"{recorded:.2f}{units} (limit {max_ratio:.1f}x = "
          f"{limit:.2f}{units})")
    if fresh > limit:
        print("baseline check: FAIL — near-full regressed "
              f"{fresh / recorded:.2f}x vs baseline")
        return 1
    print("baseline check: OK")
    return 0


def main(quick: bool = False, out: str | None = None, repeats: int = 5):
    sched = scheduling_overhead(quick=quick, repeats=repeats)
    sched["near_full"] = near_full(quick=quick, repeats=repeats)
    sched["shards_sweep"] = shards_sweep(quick=quick, repeats=repeats)
    fd = fused_dispatch(quick=quick, repeats=repeats)
    fds = fused_dispatch_static(quick=quick, repeats=repeats)
    vo = validate_overhead(quick=quick, repeats=repeats)
    st = streaming(quick=quick, repeats=repeats)
    r = run(quick=quick)
    payload = {"host_vs_device": r, "scheduling_overhead": sched,
               "fused_dispatch": fd, "fused_dispatch_static": fds,
               "validate_overhead": vo, "streaming": st}
    if out:
        Path(out).write_text(json.dumps(payload, indent=2) + "\n")
        print("wrote", out)
    if quick:
        # Quick mode uses a smaller workload — don't clobber the
        # recorded full-run perf baseline future PRs track.
        print("quick mode: not overwriting", JSON_PATH.name)
    else:
        # Deep-merge, don't overwrite: sections recorded by other
        # suites (serving_fusion, --trace replays, --shards-only
        # placement rows) live in the same file, nested under keys
        # this run also writes — a shallow top-level update would
        # erase them.
        _merge_into_json(payload)
    print("events,host_us_per_event,device_us_per_event,device_speedup")
    print(f"{r['events']},{r['host_us_per_event']:.1f},"
          f"{r['device_us_per_event']:.1f},{r['device_speedup']:.2f}")
    pb = sched["anchor"]["per_batch_us"]
    print(f"scheduling us/batch @ cap={sched['anchor']['capacity']} "
          f"k={sched['anchor']['max_batch_len']}: "
          f"tiered3={pb['tiered3']:.1f} tiered={pb['tiered']:.1f} "
          f"flat={pb['flat']:.1f} reference={pb['reference']:.1f} "
          f"(tiered vs ref {pb['speedup_tiered_vs_reference']:.2f}x)")
    for cap, row in sched["capacity_sweep"]["capacities"].items():
        print(f"  cap={cap:>6}: tiered3 per_batch="
              f"{row['tiered3']['per_batch_us']:.1f}us insert="
              f"{row['tiered3']['insert_op_us']:.1f}us | tiered per_batch="
              f"{row['tiered']['per_batch_us']:.1f}us insert="
              f"{row['tiered']['insert_op_us']:.1f}us | flat per_batch="
              f"{row['flat']['per_batch_us']:.1f}us insert="
              f"{row['flat']['insert_op_us']:.1f}us")
    ratio = sched["capacity_sweep"]["insert_op_ratio_16k_over_1k"]
    r3 = sched["capacity_sweep"]["tiered3_insert_op_ratio_16k_over_1k"]
    if ratio is not None:
        print(f"capacity-independence: insert 16k/1k tiered={ratio:.2f}x "
              f"tiered3={r3:.2f}x")
    _print_near_full(sched["near_full"])
    _print_shards(sched["shards_sweep"])
    _print_fused(fd)
    _print_fused_static(fds)
    _print_validate(vo)
    _print_streaming(st)
    if not quick:
        print(f"wrote {JSON_PATH}")
    r = dict(r)
    r["sched_speedup"] = pb["speedup_tiered_vs_reference"]
    return r


if __name__ == "__main__":
    import argparse

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--near-full-only", action="store_true",
                    help="run just the near-full stress and merge it "
                         "into the recorded JSON baseline")
    ap.add_argument("--shards-only", action="store_true",
                    help="run just the sharded-engine sweep (shards "
                         "1/2/4, interleaved rounds) and merge it into "
                         "the recorded JSON baseline")
    ap.add_argument("--fused-only", action="store_true",
                    help="run just the dispatch-specialization "
                         "comparison (switch/masked/fused) and merge it "
                         "into the recorded JSON baseline")
    ap.add_argument("--validate-only", action="store_true",
                    help="run just the validate='cheap' vs 'off' "
                         "interleaved A/B and merge it into the "
                         "recorded JSON baseline")
    ap.add_argument("--streaming-only", action="store_true",
                    help="run just the open-system ingestion section "
                         "(streamed vs pre-seeded, sync vs prefetch "
                         "feed, bounded-memory spill) and merge it "
                         "into the recorded JSON baseline")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="with --streaming-only: also replay this "
                         "on-disk trace (scripts/gen_trace.py) through "
                         "the bounded streamed config and record the "
                         "acceptance-scale trace_replay subsection")
    ap.add_argument("--check-streaming", type=float, default=None,
                    metavar="RATIO",
                    help="with --streaming-only: exit 1 unless the "
                         "streamed run is bit-identical to the "
                         "pre-seeded reference and within RATIO x of "
                         "its wall time (absolute ceiling; CI gate "
                         "for the ingestion path)")
    ap.add_argument("--check-shards", type=float, default=None,
                    metavar="RATIO",
                    help="with --shards-only: exit 1 if the fresh "
                         "devices/serial per-batch ratio at the widest "
                         "shard count exceeds RATIO (absolute ceiling; "
                         "CI gate for the shard_map placement — needs "
                         "the forced-device XLA_FLAGS)")
    ap.add_argument("--check-validate", type=float, default=None,
                    metavar="RATIO",
                    help="with --validate-only: exit 1 if the fresh "
                         "cheap/off per-batch ratio exceeds RATIO "
                         "(absolute ceiling; CI gate for the on-device "
                         "invariant auditor)")
    ap.add_argument("--repeats", type=int, default=5,
                    help="whole-run timing samples per measurement; the "
                         "recorded value is the median (raw samples are "
                         "kept alongside)")
    ap.add_argument("--check-baseline", type=float, default=None,
                    metavar="RATIO",
                    help="with --near-full-only / --fused-only: compare "
                         "the fresh medians (tiered3 near-full ratio / "
                         "fused-over-masked dispatch ratio) against the "
                         "recorded baseline instead of merging; exit 1 "
                         "on a >RATIO x regression (CI perf gate)")
    ap.add_argument("--out", default=None,
                    help="also write results to this path (CI artifact)")
    args = ap.parse_args()
    if args.shards_only:
        sh = shards_sweep(quick=args.quick, repeats=args.repeats)
        _print_shards(sh)
        if args.out:
            Path(args.out).write_text(json.dumps({"shards_sweep": sh},
                                                 indent=2) + "\n")
        if args.check_shards is not None:
            raise SystemExit(_check_shards(sh, args.check_shards))
        if args.quick:
            print("quick mode: not merging into", JSON_PATH.name)
        else:
            _merge_shards_into_json(sh)
            print("merged shards_sweep into", JSON_PATH.name)
    elif args.fused_only:
        fd = fused_dispatch(quick=args.quick, repeats=args.repeats)
        fds = fused_dispatch_static(quick=args.quick,
                                    repeats=args.repeats)
        _print_fused(fd)
        _print_fused_static(fds)
        if args.out:
            Path(args.out).write_text(json.dumps(
                {"fused_dispatch": fd, "fused_dispatch_static": fds},
                indent=2) + "\n")
        if args.check_baseline is not None:
            rc = _check_fused_baseline(fd, args.check_baseline)
            rc = max(rc, _check_fused_static_baseline(
                fds, args.check_baseline))
            raise SystemExit(rc)
        if args.quick:
            print("quick mode: not merging into", JSON_PATH.name)
        else:
            _merge_fused_into_json(fd, fds)
            print("merged fused_dispatch + fused_dispatch_static into",
                  JSON_PATH.name)
    elif args.streaming_only:
        st = streaming(quick=args.quick, repeats=args.repeats,
                       trace=args.trace)
        _print_streaming(st)
        if args.out:
            Path(args.out).write_text(
                json.dumps({"streaming": st}, indent=2) + "\n")
        if args.check_streaming is not None:
            raise SystemExit(_check_streaming(st, args.check_streaming))
        if args.quick:
            print("quick mode: not merging into", JSON_PATH.name)
        else:
            _merge_streaming_into_json(st)
            print("merged streaming into", JSON_PATH.name)
    elif args.validate_only:
        vo = validate_overhead(quick=args.quick, repeats=args.repeats)
        _print_validate(vo)
        if args.out:
            Path(args.out).write_text(
                json.dumps({"validate_overhead": vo}, indent=2) + "\n")
        if args.check_validate is not None:
            raise SystemExit(_check_validate_overhead(
                vo, args.check_validate))
        if args.quick:
            print("quick mode: not merging into", JSON_PATH.name)
        else:
            _merge_validate_into_json(vo)
            print("merged validate_overhead into", JSON_PATH.name)
    elif args.near_full_only:
        # The gate reads only the anchor — skip the capacity sweep.
        nf = near_full(quick=args.quick, repeats=args.repeats,
                       sweep=args.check_baseline is None,
                       controls=args.check_baseline is None)
        _print_near_full(nf)
        if args.out:
            Path(args.out).write_text(json.dumps({"near_full": nf},
                                                 indent=2) + "\n")
        if args.check_baseline is not None:
            raise SystemExit(_check_near_full_baseline(
                nf, args.check_baseline))
        if args.quick:
            print("quick mode: not merging into", JSON_PATH.name)
        else:
            _merge_near_full_into_json(nf)
            print("merged near_full into", JSON_PATH.name)
    else:
        main(quick=args.quick, out=args.out, repeats=args.repeats)
