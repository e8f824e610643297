"""Pending-event set: host binary heap + device-resident array queue.

The paper's runtime mechanism reads the set of future events in
non-decreasing timestamp order (§III-B).  Two implementations:

* :class:`HostEventQueue` — a classic binary heap over
  :class:`repro.core.events.Event`, used by the paper-faithful host
  scheduler and by the serving engine's host control plane.

* :class:`DeviceEventQueue` — a fixed-capacity struct-of-arrays queue
  whose operations are pure jnp (usable inside ``lax.while_loop``), used
  by the fully on-device scheduler.

Device queue layout
-------------------
``types == -1`` marks a free slot, and free slots always hold the
sentinel key ``(time=+inf, seq=i32_max)`` so they order after every real
event.  ``seq`` is the global insertion counter used for deterministic
``(time, seq)`` lexicographic pop order.  ``size`` counts *logical*
pushes (it keeps incrementing past ``capacity`` on overflow so callers
can detect it); ``dropped`` counts events lost to overflow.

Two families of operations are provided:

* **Reference ops** (seed semantics, layout-independent, O(capacity)
  work *per event* with a serial dependence chain):
  :func:`device_queue_peek`, :func:`device_queue_pop`,
  :func:`device_queue_push`, :func:`device_queue_push_rows`,
  :func:`device_queue_extract_ref`.  Pop is a masked argmin; push is a
  first-free-slot scatter.  Kept as the executable specification for
  differential tests.

* **Vectorized single-pass ops**, which require and preserve the
  *canonical layout*: occupied slots form a prefix of the arrays,
  ordered by ``(time, seq)`` (:func:`device_queue_from_host` builds it;
  an empty queue has it trivially).  With the pending set kept sorted,
  every per-batch interaction is a constant number of fused
  data-parallel passes — no sorts, no reductions, no serial chains:

  - :func:`device_queue_extract` reads the lookahead window directly
    from the first ``max_batch_len`` slots, evaluates the §III-B
    dynamic-lookahead take rule as a shifted ``cummin`` + prefix mask
    (:func:`window_prefix_mask` — the rule is monotone on time-sorted
    candidates, so no serial scan is needed), and pops all taken slots
    by shifting each column left with one ``dynamic_slice``.

  - :func:`device_queue_fill_rows` merges a whole emit block at once:
    merge positions come from all-pairs key comparisons
    (rows × capacity fused bools, a counting merge), and each column is
    rebuilt with a single gather/select pass.

  Both reproduce the reference ops' ``(time, seq)`` pop order and
  overflow behaviour bit-exactly; the two families must not be
  interleaved on one queue (the reference pushes do not maintain the
  canonical layout).

* **Tiered ops** (DESIGN.md §4) over :class:`TieredDeviceQueue`, which
  splits the pending set into a small sorted *front* tier (the globally
  earliest events), an unsorted *staging* ring, and the capacity-sized
  sorted *main* array, with the invariant ``max(front) <= min(staging
  ∪ main)`` under the ``(time, seq)`` key.  Per-batch work touches only
  the front and staging tiers — O(front_cap) regardless of capacity:

  - :func:`tiered_queue_extract` reads the window from the front tier
    (same shifted-cummin take rule); when the front has drained below
    ``max_len`` it first refills from the main array (a rare
    ``lax.cond`` path, amortized to ~zero per batch).

  - :func:`tiered_queue_fill_rows` counting-merges emit rows whose
    timestamp precedes the tier boundary into the front (evicting the
    front tail to staging when full) and appends the rest to staging;
    staging is bulk-merged into the main array only when it could
    overflow on the next batch or the front drains.

  The tiered ops reproduce the flat/reference ``(time, seq)`` pop order
  and the ``size``/``next_seq``/``dropped`` accounting bit-exactly;
  the logical capacity of the whole tiered queue equals the main
  array's capacity (front and staging are structure, not extra room).

* **Log-structured tiered ops** (DESIGN.md §4.4) over
  :class:`Tiered3DeviceQueue`: the two-tier design's one remaining
  O(capacity) path — the staging flush's lex merge + ring compaction,
  which near-full workloads with near-head re-emits hit every few
  batches — is replaced by a pool of fixed-size **sorted runs**:

  - a staging flush lex-sorts the ring and writes it as one new run
    (O(stage_cap²) fused bools + one row scatter, capacity-independent);

  - a front refill is a *bounded* k-way merge: the first ``front_cap``
    remainder elements of every run plus the main head window are
    lex-sorted by their true ``(time, seq)`` keys and the earliest
    slots are consumed by advancing per-run offsets — O(num_runs ·
    front_cap) work, no put-back, no tag bookkeeping (true seqs make
    the order exact, so the two-tier ``s_evict`` machinery disappears);

  - only when the run pool is exhausted do the runs merge into the
    main array, and the main ring carries ``num_runs × stage_cap``
    physical slack slots so that merge is usually a bounded tail
    append — otherwise the pool merges with the sorted ring in linear
    time (a binary-search rank per pool element, then shift passes over
    the ring; no sort of the ring), amortized over an entire pool of
    staged events and never on the per-batch path.

  Same bit-exact contract and logical-capacity rule as the other
  families (``capacity`` excludes the slack; front/staging/runs are
  structure, not room).
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.events import ARG_WIDTH, Event
from repro.core.spans import ABSORB, MERGE

_INF = jnp.float32(jnp.inf)
_I32_MAX = jnp.int32(2**31 - 1)


class HostEventQueue:
    """Binary heap of Events keyed by (time, seq)."""

    def __init__(self):
        self._heap: list[tuple[float, int, Event]] = []
        self._seq = 0
        self.push_count = 0
        self.pop_count = 0

    def push(self, time: float, type_id: int, arg: Any = None) -> Event:
        ev = Event(time=float(time), type_id=int(type_id), arg=arg, seq=self._seq)
        heapq.heappush(self._heap, (ev.time, ev.seq, ev))
        self._seq += 1
        self.push_count += 1
        return ev

    def push_event(self, ev: Event) -> None:
        """Re-insert an existing event, PRESERVING its seq.

        Used by speculative rollback: re-pushed events must keep their
        original tie-break rank, otherwise they would sort after
        same-timestamp events that were never extracted and execution
        order would diverge from the sequential one.
        """
        heapq.heappush(self._heap, (ev.time, ev.seq, ev))
        self._seq = max(self._seq, ev.seq + 1)
        self.push_count += 1

    def pop(self) -> Event:
        self.pop_count += 1
        return heapq.heappop(self._heap)[2]

    def peek(self) -> Event:
        return self._heap[0][2]

    def __len__(self) -> int:
        return len(self._heap)

    def __bool__(self) -> bool:
        return bool(self._heap)


class DeviceQueue(NamedTuple):
    """Struct-of-arrays pending-event set (a JAX pytree).

    ``types == -1`` marks a free slot.  ``seq`` is the global insertion
    counter used for deterministic tie-breaking.  ``dropped`` counts
    events lost to capacity overflow (surfaced in the engine run stats).
    """

    times: jnp.ndarray   # f32[capacity]
    types: jnp.ndarray   # i32[capacity], -1 = empty
    args: jnp.ndarray    # f32[capacity, ARG_WIDTH]
    seqs: jnp.ndarray    # i32[capacity]
    size: jnp.ndarray    # i32 scalar
    next_seq: jnp.ndarray  # i32 scalar
    dropped: jnp.ndarray   # i32 scalar, overflow-dropped event count

    @property
    def capacity(self) -> int:
        return self.times.shape[0]


def device_queue_init(capacity: int, arg_width: int = ARG_WIDTH) -> DeviceQueue:
    return DeviceQueue(
        times=jnp.full((capacity,), jnp.inf, jnp.float32),
        types=jnp.full((capacity,), -1, jnp.int32),
        args=jnp.zeros((capacity, arg_width), jnp.float32),
        seqs=jnp.full((capacity,), 2**31 - 1, jnp.int32),
        size=jnp.int32(0),
        next_seq=jnp.int32(0),
        dropped=jnp.int32(0),
    )


def _host_sorted_seed(events, capacity: int, arg_width: int, seqs=None):
    """Shared host-side seed build: the surviving events as columns
    sorted by ``(time, seq)``, plus the logical counters.

    Semantically identical to serial reference pushes — ``seq`` runs
    0..N-1 and events past ``capacity`` are dropped with
    ``size``/``next_seq`` still advancing.  Both ``*_from_host``
    builders split these columns into their own layouts, so the
    reference overflow/seq semantics live in exactly one place.

    ``seqs`` optionally supplies explicit per-event seqs (the sharded
    engine seeds each shard with its slice of the GLOBAL seed, keeping
    the global seq discipline); explicit-seq seeds must fit — the
    global overflow rule was already applied upstream.
    """
    events = list(events)
    n = len(events)
    if seqs is not None:
        if len(seqs) != n:
            raise ValueError(
                f"{len(seqs)} explicit seqs for {n} seed events"
            )
        if n > capacity:
            raise ValueError(
                f"explicit-seq seed of {n} events exceeds capacity "
                f"{capacity}: apply the overflow rule before sharding"
            )
    m = min(n, capacity)
    times = np.full((m,), np.inf, np.float32)
    types = np.full((m,), -1, np.int32)
    args = np.zeros((m, arg_width), np.float32)
    seq_col = np.zeros((m,), np.int32)
    for i, (t, ty, arg) in enumerate(events[:m]):
        times[i] = t
        types[i] = ty
        if arg is not None:
            args[i] = np.asarray(arg, np.float32)
        seq_col[i] = i if seqs is None else int(seqs[i])
    order = np.lexsort((seq_col, times))
    return (times[order], types[order], args[order], seq_col[order], n, m)


def device_queue_from_host(
    events, capacity: int, arg_width: int = ARG_WIDTH
) -> DeviceQueue:
    """Build a seed queue host-side and move it in ONE device_put.

    ``events`` is a sequence of ``(time, type_id, arg)`` with ``arg``
    either ``None`` or an ``f32[arg_width]`` vector.  Semantically
    identical to ``device_queue_push`` applied in order — slot ``i``
    holds event ``i``, ``seq`` runs 0..N-1, events past ``capacity``
    are dropped with ``size``/``next_seq`` still advancing — but costs
    one transfer instead of N jitted dispatches.

    Canonical layout (see module docstring): occupied slots form a
    prefix sorted by (time, seq).  The reference ops are
    layout-independent; the vectorized ops require and preserve it.
    """
    st, sy, sa, ss, n, m = _host_sorted_seed(events, capacity, arg_width)
    times = np.full((capacity,), np.inf, np.float32)
    types = np.full((capacity,), -1, np.int32)
    args = np.zeros((capacity, arg_width), np.float32)
    seqs = np.full((capacity,), 2**31 - 1, np.int32)
    times[:m], types[:m], args[:m], seqs[:m] = st, sy, sa, ss
    return jax.device_put(DeviceQueue(
        times=times,
        types=types,
        args=args,
        seqs=seqs,
        size=np.int32(n),
        next_seq=np.int32(n),
        dropped=np.int32(n - m),
    ))


# ---------------------------------------------------------------------------
# Reference per-event ops (seed semantics; executable specification)
# ---------------------------------------------------------------------------

def device_queue_push(q: DeviceQueue, time, type_id, arg) -> DeviceQueue:
    """Insert one event into the first free slot (pure jnp).

    If the queue is full the event is dropped, the ``dropped`` counter
    increments, and ``size``/``next_seq`` still advance so callers can
    detect overflow (the engine surfaces ``dropped`` in its run stats).
    """
    occupied = q.types >= 0
    # argmin over the boolean mask finds the first False (free) slot.
    slot = jnp.argmin(occupied)
    have_room = q.size < q.capacity
    time = jnp.asarray(time, jnp.float32)
    type_id = jnp.asarray(type_id, jnp.int32)
    arg = jnp.asarray(arg, jnp.float32)

    def do_push(q):
        return q._replace(
            times=q.times.at[slot].set(time),
            types=q.types.at[slot].set(type_id),
            args=q.args.at[slot].set(arg),
            seqs=q.seqs.at[slot].set(q.next_seq),
            size=q.size + 1,
            next_seq=q.next_seq + 1,
        )

    def overflow(q):
        return q._replace(
            size=q.size + 1, next_seq=q.next_seq + 1, dropped=q.dropped + 1
        )

    return jax.lax.cond(have_room, do_push, overflow, q)


def device_queue_push_rows_serial(q: DeviceQueue, rows) -> DeviceQueue:
    """Seed bulk insert: one serial ``device_queue_push`` per row.

    Row layout is ``(time, type, arg...)``; ``type < 0`` rows are
    skipped.  O(rows × capacity) with a serial dependence chain — kept
    as the executable specification for :func:`device_queue_push_rows`
    and :func:`device_queue_fill_rows` (differential tests prove both
    bit-identical to this, the push-rows one including slot placement).
    """
    def body(i, q):
        row = rows[i]
        t, ty = row[0], row[1].astype(jnp.int32)
        return jax.lax.cond(
            ty >= 0,
            lambda q: device_queue_push(q, t, ty, row[2:]),
            lambda q: q,
            q,
        )

    return jax.lax.fori_loop(0, rows.shape[0], body, q)


def device_queue_push_rows(q: DeviceQueue, rows) -> DeviceQueue:
    """Reference bulk insert as ONE scatter pass (layout-independent).

    Bit-identical to :func:`device_queue_push_rows_serial` INCLUDING
    slot placement: serial pushes fill free slots in ascending slot
    order, so the row with insert-rank ``k`` lands in the ``k``-th free
    slot — all destinations are known up front and every column is one
    ``R``-row scatter instead of ``R`` chained O(capacity) argmin/cond
    rounds.  Valid row ``r`` gets ``seq = next_seq + vrank(r)`` and is
    dropped iff ``size + vrank(r) >= capacity`` (``size`` counts ghosts
    — the serial ``have_room`` check at the moment row ``r`` pushes),
    with ``size``/``next_seq`` still advancing and ``dropped`` counted.
    """
    rows = jnp.asarray(rows, jnp.float32)
    C = q.capacity
    t_r = rows[:, 0]
    ty_r = rows[:, 1].astype(jnp.int32)
    arg_r = rows[:, 2:]

    valid = ty_r >= 0
    vrank = _prefix_rank(valid)
    num_valid = jnp.sum(valid).astype(jnp.int32)
    insert = valid & (q.size + vrank < C)
    num_insert = jnp.sum(insert).astype(jnp.int32)
    seq_r = q.next_seq + vrank

    # k-th free slot: rank the free slots by cumsum, invert by scatter.
    # `size >= occupancy` guarantees every inserting row finds a free
    # slot (insert-rank < C - size <= number of free slots).
    free = q.types < 0
    free_rank = jnp.cumsum(free).astype(jnp.int32) - 1
    slot_of_rank = jnp.full((C,), C, jnp.int32).at[
        jnp.where(free, free_rank, C)
    ].set(jnp.arange(C, dtype=jnp.int32), mode="drop")
    irank = _prefix_rank(insert)
    dest = jnp.where(
        insert, slot_of_rank[jnp.clip(irank, 0, C - 1)], C
    )

    return q._replace(
        times=q.times.at[dest].set(t_r, mode="drop"),
        types=q.types.at[dest].set(ty_r, mode="drop"),
        args=q.args.at[dest].set(arg_r, mode="drop"),
        seqs=q.seqs.at[dest].set(seq_r, mode="drop"),
        size=q.size + num_valid,
        next_seq=q.next_seq + num_valid,
        dropped=q.dropped + (num_valid - num_insert),
    )


def _min_key_slot(q: DeviceQueue):
    """Index of the occupied slot with lexicographic-min (time, seq)."""
    occupied = q.types >= 0
    times = jnp.where(occupied, q.times, jnp.inf)
    tmin = jnp.min(times)
    at_min = occupied & (times == tmin)
    seqs = jnp.where(at_min, q.seqs, _I32_MAX)
    slot = jnp.argmin(seqs)
    return slot, tmin


def device_queue_peek(q: DeviceQueue):
    """(time, type, slot) of the earliest event; type=-1 when empty."""
    slot, tmin = _min_key_slot(q)
    empty = q.size <= 0
    t = jnp.where(empty, _INF, tmin)
    ty = jnp.where(empty, jnp.int32(-1), q.types[slot])
    return t, ty, slot


def device_queue_pop(q: DeviceQueue):
    """Remove and return the earliest event.

    Returns ``(q', time, type, arg)``; when empty, type is -1 and the
    queue is unchanged.
    """
    t, ty, slot = device_queue_peek(q)
    arg = q.args[slot]
    nonempty = ty >= 0

    def do_pop(q):
        return q._replace(
            times=q.times.at[slot].set(jnp.inf),
            types=q.types.at[slot].set(-1),
            seqs=q.seqs.at[slot].set(2**31 - 1),
            size=q.size - 1,
        )

    q = jax.lax.cond(nonempty, do_pop, lambda q: q, q)
    return q, t, ty, arg


def device_queue_next_time(q: DeviceQueue):
    """Earliest pending timestamp under the canonical layout (O(1)).

    The occupied prefix is (time, seq)-sorted, so the head slot answers;
    an empty queue holds the ``inf`` sentinel there.
    """
    return q.times[0]


def device_queue_next_time_ref(q: DeviceQueue):
    """Earliest pending timestamp, layout-independent (O(capacity))."""
    return jnp.min(jnp.where(q.types >= 0, q.times, _INF))


def device_queue_extract_ref(q: DeviceQueue, max_len: int, lookaheads,
                             t_cap=None):
    """Reference window extraction: ``max_len`` serial peek/pop rounds.

    The seed engine's loop (paper Fig 2 evaluated one event at a time):
    each round is an O(capacity) masked argmin inside ``lax.cond``, with
    a serial dependence between rounds.  ``t_cap`` optionally starts the
    dynamic window bound below ``inf`` (the run horizon).  Returns
    ``(q', ts, tys, args, length)`` with zero-padding past ``length``.
    Kept as the executable specification for
    :func:`device_queue_extract`.
    """
    ts0 = jnp.zeros((max_len,), jnp.float32)
    tys0 = jnp.zeros((max_len,), jnp.int32)
    args0 = jnp.zeros((max_len, q.args.shape[1]), jnp.float32)

    def body(i, carry):
        queue, ts, tys, args, length, t_max, done = carry
        t, ty, _slot = device_queue_peek(queue)
        can_take = (~done) & (ty >= 0) & (t <= t_max)

        def take(_):
            q2, t2, ty2, arg2 = device_queue_pop(queue)
            ts2 = ts.at[i].set(t2)
            tys2 = tys.at[i].set(ty2)
            args2 = args.at[i].set(arg2)
            t_max2 = jnp.minimum(t_max, t2 + lookaheads[ty2])
            return q2, ts2, tys2, args2, length + 1, t_max2, done

        def skip(_):
            return queue, ts, tys, args, length, t_max, jnp.bool_(True)

        return jax.lax.cond(can_take, take, skip, None)

    cap = _INF if t_cap is None else jnp.asarray(t_cap, jnp.float32)
    init = (q, ts0, tys0, args0, jnp.int32(0), cap, jnp.bool_(False))
    q, ts, tys, args, length, _t_max, _done = jax.lax.fori_loop(
        0, max_len, body, init
    )
    return q, ts, tys, args, length


# ---------------------------------------------------------------------------
# Vectorized single-pass ops
# ---------------------------------------------------------------------------

def _small_lex_perm(ts, sq):
    """Permutation sorting a TINY vector by (ts, sq, index) ascending.

    XLA:CPU sorts are custom calls with large fixed overhead, so for the
    k-element candidate vectors (k = max_batch_len class) the rank of
    each element is computed from all-pairs comparisons (m² tiny bools,
    fully fused) and inverted with an m-element scatter.
    """
    m = ts.shape[0]
    i = jnp.arange(m, dtype=jnp.int32)
    t_lt = ts[:, None] > ts[None, :]
    t_eq = ts[:, None] == ts[None, :]
    s_lt = sq[:, None] > sq[None, :]
    s_eq = sq[:, None] == sq[None, :]
    before = t_lt | (t_eq & s_lt) | (t_eq & s_eq & (i[:, None] > i[None, :]))
    rank = jnp.sum(before, axis=1).astype(jnp.int32)  # unique in [0, m)
    return jnp.zeros((m,), jnp.int32).at[rank].set(i)


def _prefix_rank(mask):
    """Rank of each position among the True positions of a TINY mask
    (-1 where False counts itself out), via all-pairs counting — the
    same avoid-a-scan-thunk reasoning as :func:`_small_lex_perm`."""
    n = mask.shape[0]
    i = jnp.arange(n, dtype=jnp.int32)
    return jnp.sum(
        (i[None, :] <= i[:, None]) & mask[None, :], axis=1
    ).astype(jnp.int32) - 1


def window_prefix_mask(ts, wins, valid, t_cap=None):
    """Vectorized §III-B dynamic-lookahead take rule.

    Given candidates already sorted by ``(time, seq)``, the serial rule
    — take event ``i`` iff every earlier candidate was taken and
    ``t_i <= t_max`` where ``t_max = min over taken j<i of (t_j + l_j)``
    — is *monotone*: once a candidate is rejected no later one can be
    taken.  It therefore reduces to two scans: a shifted (exclusive)
    ``cummin`` over the window bounds ``wins = t + l``, and a prefix-AND
    (via cumsum of rejections) that implements the stop condition.

    ``t_cap`` initializes the dynamic bound below ``inf`` — the run
    horizon (``until``): with it, no event past the cap is ever taken,
    the cross-backend ``t_end`` contract.

    Shared with :func:`repro.core.scheduler.extract_window`, which is
    the host/serial form of the same rule; the differential tests assert
    their equivalence.
    """
    ts = jnp.asarray(ts, jnp.float32)
    wins = jnp.asarray(wins, jnp.float32)
    cap = _INF if t_cap is None else jnp.asarray(t_cap, jnp.float32)
    # Exclusive cummin of the window bounds: t_max before candidate i.
    t_max = jnp.concatenate(
        [jnp.full((1,), jnp.inf, jnp.float32), jax.lax.cummin(wins)[:-1]]
    )
    ok = valid & (ts <= jnp.minimum(t_max, cap))
    # Prefix-AND: no rejection at any earlier position.
    return jnp.cumsum(~ok) == 0


def device_queue_extract(q: DeviceQueue, max_len: int, lookaheads,
                         t_cap=None):
    """Single-pass window extraction (paper Fig 2, fully vectorized).

    Requires the canonical sorted layout (occupied slots form a prefix
    ordered by ``(time, seq)`` — see the module docstring), which makes
    the ``max_len`` earliest events simply the first ``max_len`` slots:
    no reductions, no sort, no serial dependence.  The dynamic lookahead
    rule is applied with :func:`window_prefix_mask`, and all taken slots
    are popped at once by shifting every column left by ``length`` (one
    fused ``dynamic_slice`` per column) — preserving the invariant.

    Bit-identical batch output to :func:`device_queue_extract_ref`
    (lexicographic pop order, tie-breaks, zero-padding) at a constant
    number of data-parallel passes per *batch* instead of
    O(max_len × capacity) serially dependent work.

    Returns ``(q', ts, tys, args, length)``.
    """
    if max_len > q.capacity:
        raise ValueError(
            f"max_len {max_len} exceeds queue capacity {q.capacity}"
        )
    k = max_len
    cap = q.capacity
    num_types = lookaheads.shape[0]
    ts_c = q.times[:k]
    tys_c = q.types[:k]

    valid = tys_c >= 0
    la = lookaheads[jnp.clip(tys_c, 0, num_types - 1)]
    wins = jnp.where(valid, ts_c + la, jnp.inf)
    take = window_prefix_mask(ts_c, wins, valid, t_cap)
    length = jnp.sum(take).astype(jnp.int32)

    ts = jnp.where(take, ts_c, 0.0)
    tys = jnp.where(take, tys_c, 0)
    args = jnp.where(take[:, None], q.args[:k], 0.0)

    # Pop the taken prefix: shift every column left by `length`,
    # refilling the tail with the free-slot sentinels.
    def shift(col, fill):
        pad = jnp.full((k,) + col.shape[1:], fill, col.dtype)
        return jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([col, pad]), length, cap
        )

    q = q._replace(
        times=shift(q.times, jnp.inf),
        types=shift(q.types, -1),
        args=shift(q.args, 0.0),
        seqs=shift(q.seqs, 2**31 - 1),
        size=q.size - length,
    )
    return q, ts, tys, args, length


def device_queue_fill_rows(q: DeviceQueue, rows) -> DeviceQueue:
    """Bulk emit insert: merge a whole ``f32[R, 2+W]`` block at once.

    Row layout is ``(time, type, arg...)``; ``type < 0`` rows are
    skipped.  Requires and preserves the canonical sorted layout: valid
    row ``j`` (the ``r``-th valid row) receives ``seq = next_seq + r``
    — exactly the seq assignment of :func:`device_queue_push_rows` —
    and the surviving rows are merged into the sorted queue in one
    vectorized counting-merge: every merge position is computed from
    all-pairs key comparisons (R·capacity fused bools, no sort, no
    scan), and each queue column is rebuilt with a single gather/select
    pass.  Rows past capacity are dropped with ``size``/``next_seq``
    still advancing and ``dropped`` counted, matching the reference
    overflow semantics.
    """
    rows = jnp.asarray(rows, jnp.float32)
    R = rows.shape[0]
    C = q.capacity
    t_r = rows[:, 0]
    ty_r = rows[:, 1].astype(jnp.int32)
    arg_r = rows[:, 2:]

    valid = ty_r >= 0
    # Rank of each row among the valid rows (R is tiny).
    r_idx = jnp.arange(R, dtype=jnp.int32)
    vrank = _prefix_rank(valid)
    num_valid = jnp.sum(valid).astype(jnp.int32)
    # Serial-push overflow rule: row r inserts iff size + r < capacity
    # (size counts logical pushes, so it may already exceed occupancy).
    insert = valid & (q.size + vrank < C)
    num_insert = jnp.sum(insert).astype(jnp.int32)
    seq_r = q.next_seq + vrank

    # Order the surviving rows by (time, arrival): arrival order equals
    # seq order, and dropped rows are pushed past everything real.
    perm = _small_lex_perm(
        jnp.where(insert, t_r, jnp.inf),
        jnp.where(insert, r_idx, _I32_MAX),
    )
    rt = jnp.where(insert, t_r, jnp.inf)[perm]
    rty = ty_r[perm]
    rarg = arg_r[perm]
    rseq = seq_r[perm]
    rins = insert[perm]

    # Merge positions.  Keys are strictly totally ordered: row seqs are
    # all >= next_seq while queued seqs are all < next_seq, so EVERY
    # equal-time queued event precedes the new row — the count of queued
    # events before row r is therefore a plain searchsorted(side=right)
    # over the sorted times, capped at the occupancy so the (+inf,
    # i32_max) free-slot sentinels are never counted.
    # pos[r] = (#queued events before row r) + r, the second term
    # counting the earlier (sorted, inserting) rows.
    occupancy = jnp.sum(q.types >= 0).astype(jnp.int32)
    older = jnp.minimum(
        jnp.searchsorted(q.times, rt, side="right").astype(jnp.int32),
        occupancy,
    )
    pos = jnp.where(rins, older + r_idx, C)

    # Rebuild each column with one gather pass: output slot i holds
    # sorted row `ins_before[i]` if some row lands at i, else the queued
    # entry shifted right by the rows inserted before it.
    i_idx = jnp.arange(C, dtype=jnp.int32)
    ins_before = jnp.sum(pos[None, :] < i_idx[:, None], axis=1).astype(
        jnp.int32
    )
    is_ins = jnp.sum(pos[None, :] == i_idx[:, None], axis=1) > 0
    src = jnp.where(
        is_ins, C + jnp.clip(ins_before, 0, R - 1),
        jnp.clip(i_idx - ins_before, 0, C - 1),
    )

    def merge(col, rcol):
        return jnp.take(jnp.concatenate([col, rcol]), src, axis=0)

    return q._replace(
        times=merge(q.times, rt),
        types=merge(q.types, rty),
        args=merge(q.args, rarg),
        seqs=merge(q.seqs, rseq),
        size=q.size + num_valid,
        next_seq=q.next_seq + num_valid,
        dropped=q.dropped + (num_valid - num_insert),
    )


# ---------------------------------------------------------------------------
# Two-tier queue: front / staging / main (DESIGN.md §4)
# ---------------------------------------------------------------------------

class TieredDeviceQueue(NamedTuple):
    """Pending-event set split into three tiers (a JAX pytree).

    * ``f_*`` — the **front** tier: ``front_cap`` slots in canonical
      layout (occupied prefix sorted by ``(time, seq)``), holding the
      globally earliest pending events.  Every per-batch operation
      touches only this tier (plus the staging ring), so per-batch cost
      is O(front_cap), independent of ``capacity``.
    * ``s_*`` — the **staging** ring: ``stage_cap`` slots of events that
      sort after the front boundary, in arrival order.  Bulk-merged into
      the main array only when it could overflow or the front drains.
    * ``m_*`` — the **main** array: ``capacity`` slots holding the far
      future as a head-offset ring: the logical (sorted) main tier is
      the ``main_n`` slots starting at ``m_head``.  Refills consume
      from the head without shifting, staging flushes append sorted
      blocks at the tail, and the slots before ``m_head`` are dead
      (stale, NOT sentinel-cleared) until a merge flush compacts the
      ring back to ``m_head = 0``.

    Tier invariant: ``max(front) <= min(staging ∪ main)`` under the
    lexicographic ``(time, seq)`` key.  ``size``/``next_seq``/``dropped``
    follow the reference semantics exactly (``size`` counts logical
    pushes including overflow ghosts); the *logical* capacity is
    ``capacity`` — the front and staging arrays add structure, not room.
    """

    f_times: jnp.ndarray   # f32[front_cap]
    f_types: jnp.ndarray   # i32[front_cap], -1 = empty
    f_args: jnp.ndarray    # f32[front_cap, ARG_WIDTH]
    f_seqs: jnp.ndarray    # i32[front_cap]
    m_times: jnp.ndarray   # f32[capacity]
    m_types: jnp.ndarray   # i32[capacity]
    m_args: jnp.ndarray    # f32[capacity, ARG_WIDTH]
    m_seqs: jnp.ndarray    # i32[capacity]
    s_times: jnp.ndarray   # f32[stage_cap]
    s_types: jnp.ndarray   # i32[stage_cap]
    s_args: jnp.ndarray    # f32[stage_cap, ARG_WIDTH]
    s_seqs: jnp.ndarray    # i32[stage_cap]
    s_evict: jnp.ndarray   # bool[stage_cap], True = evicted from front
    front_n: jnp.ndarray   # i32 scalar, occupied front slots
    main_n: jnp.ndarray    # i32 scalar, occupied main slots
    m_head: jnp.ndarray    # i32 scalar, first logical main slot (ring)
    stage_n: jnp.ndarray   # i32 scalar, occupied staging slots
    size: jnp.ndarray      # i32 scalar, logical pushes (incl. ghosts)
    next_seq: jnp.ndarray  # i32 scalar
    dropped: jnp.ndarray   # i32 scalar

    @property
    def capacity(self) -> int:
        return self.m_times.shape[0]

    @property
    def front_cap(self) -> int:
        return self.f_times.shape[0]

    @property
    def stage_cap(self) -> int:
        return self.s_times.shape[0]


def _ring_unroll(col, fill, head, n, offset=0):
    """Materialize a head-offset ring column's live window at physical
    ``offset``: one O(P) gather (roll by ``head - offset``) with the
    dead slots reset to ``fill``.  Shared by every ring compaction /
    re-centering site — the roll semantics must stay identical."""
    P = col.shape[0]
    i_idx = jnp.arange(P, dtype=jnp.int32)
    rolled = jnp.take(col, (i_idx - offset + head) % P, axis=0)
    live = (i_idx >= offset) & (i_idx < offset + n)
    mask = live if col.ndim == 1 else live[:, None]
    return jnp.where(mask, rolled, fill)


def _sentinel_cols(n: int, arg_width: int):
    return (
        jnp.full((n,), jnp.inf, jnp.float32),
        jnp.full((n,), -1, jnp.int32),
        jnp.zeros((n, arg_width), jnp.float32),
        jnp.full((n,), 2**31 - 1, jnp.int32),
    )


def tiered_queue_init(capacity: int, *, front_cap: int = 256,
                      stage_cap: int = 256,
                      arg_width: int = ARG_WIDTH) -> TieredDeviceQueue:
    front_cap = min(front_cap, capacity)
    ft, fy, fa, fs = _sentinel_cols(front_cap, arg_width)
    mt, my, ma, ms = _sentinel_cols(capacity, arg_width)
    st, sy, sa, ss = _sentinel_cols(stage_cap, arg_width)
    z = jnp.int32(0)
    return TieredDeviceQueue(
        f_times=ft, f_types=fy, f_args=fa, f_seqs=fs,
        m_times=mt, m_types=my, m_args=ma, m_seqs=ms,
        s_times=st, s_types=sy, s_args=sa, s_seqs=ss,
        s_evict=jnp.zeros((stage_cap,), bool),
        front_n=z, main_n=z, m_head=z, stage_n=z, size=z, next_seq=z,
        dropped=z,
    )


def tiered_queue_from_host(events, capacity: int, *, front_cap: int = 256,
                           stage_cap: int = 256,
                           arg_width: int = ARG_WIDTH) -> TieredDeviceQueue:
    """Host-built seed queue, one device_put (cf. device_queue_from_host).

    Events are sorted by ``(time, seq)``; the earliest ``front_cap`` go
    to the front tier, the rest to the main array.  Same logical
    semantics as N serial pushes: ``seq`` runs 0..N-1 and events past
    ``capacity`` are dropped with ``size``/``next_seq`` still advancing.
    """
    front_cap = min(front_cap, capacity)
    times, types, args, seqs, n, m = _host_sorted_seed(
        events, capacity, arg_width
    )
    nf = min(m, front_cap)
    ft = np.full((front_cap,), np.inf, np.float32)
    fy = np.full((front_cap,), -1, np.int32)
    fa = np.zeros((front_cap, arg_width), np.float32)
    fs = np.full((front_cap,), 2**31 - 1, np.int32)
    ft[:nf], fy[:nf], fa[:nf], fs[:nf] = (
        times[:nf], types[:nf], args[:nf], seqs[:nf]
    )
    mt = np.full((capacity,), np.inf, np.float32)
    my = np.full((capacity,), -1, np.int32)
    ma = np.zeros((capacity, arg_width), np.float32)
    ms = np.full((capacity,), 2**31 - 1, np.int32)
    nm = m - nf
    mt[:nm], my[:nm], ma[:nm], ms[:nm] = (
        times[nf:], types[nf:], args[nf:], seqs[nf:]
    )
    st, sy, sa, ss = (np.full((stage_cap,), np.inf, np.float32),
                      np.full((stage_cap,), -1, np.int32),
                      np.zeros((stage_cap, arg_width), np.float32),
                      np.full((stage_cap,), 2**31 - 1, np.int32))
    return jax.device_put(TieredDeviceQueue(
        f_times=ft, f_types=fy, f_args=fa, f_seqs=fs,
        m_times=mt, m_types=my, m_args=ma, m_seqs=ms,
        s_times=st, s_types=sy, s_args=sa, s_seqs=ss,
        s_evict=np.zeros((stage_cap,), bool),
        front_n=np.int32(nf), main_n=np.int32(nm), m_head=np.int32(0),
        stage_n=np.int32(0),
        size=np.int32(n), next_seq=np.int32(n), dropped=np.int32(n - m),
    ))


def tiered_queue_has_pending(q: TieredDeviceQueue):
    """True while any tier holds a real event.

    ``size`` alone is wrong (it counts overflow ghosts), and the front
    head alone is wrong too — the front may be empty while staging/main
    still hold events awaiting a refill.  O(1) from the tier counters.
    """
    return (q.front_n > 0) | (q.stage_n > 0) | (q.main_n > 0)


def tiered_queue_occupancy(q: TieredDeviceQueue):
    """Number of real pending events across all tiers (O(1))."""
    return q.front_n + q.stage_n + q.main_n


def tiered_queue_next_time(q: TieredDeviceQueue):
    """Timestamp of the earliest pending event (``inf`` when empty).

    While the front is non-empty its head is the global minimum (tier
    invariant); a drained front falls back to min(staging, main head) —
    O(stage_cap) for the unsorted ring, still capacity-independent.
    """
    m_min = jnp.where(
        q.main_n > 0,
        jnp.take(q.m_times, jnp.clip(q.m_head, 0, q.capacity - 1)),
        _INF,
    )
    rest = jnp.minimum(jnp.min(q.s_times), m_min)
    return jnp.where(q.front_n > 0, q.f_times[0], rest)


def _flush_stage(q: TieredDeviceQueue) -> TieredDeviceQueue:
    """Bulk-merge the staging ring into the main array (rare path).

    Unlike the emit-row merge, staged keys need lexicographic positions
    AGAINST BOTH TIE DIRECTIONS: a fresh emit's seq exceeds every main
    seq (equal-time main events precede it -> ``searchsorted`` with
    ``side="right"``), while a front-evicted event predates every
    equal-time main event — the ``main >= front`` invariant held while
    it sat in the front, so any equal-time event that reached main has a
    LARGER seq (-> ``side="left"``).  The ``s_evict`` tag records which
    rule applies; no all-pairs seq comparison is needed.  Merge
    positions are unique, so the column rebuild reduces to a scatter
    histogram + exclusive cumsum plus one gather — a linear pass over
    the output, only on the (rarer still) merge fallback; the common
    far-future case is an O(stage_cap) tail append.  Never drops: the
    logical-capacity rule guarantees ``main_n + stage_n <= capacity``.
    """
    S = q.stage_cap
    C = q.capacity
    perm = _small_lex_perm(q.s_times, q.s_seqs)
    st = q.s_times[perm]
    sty = q.s_types[perm]
    sarg = q.s_args[perm]
    sseq = q.s_seqs[perm]
    sev = q.s_evict[perm]
    sval = sty >= 0

    # Fast path: every staged timestamp strictly exceeds the main tail
    # (the overwhelmingly common DES shape — emissions land in the
    # future) and the sorted block fits before the physical end of the
    # ring: one O(stage_cap) dynamic_update_slice at the tail.
    head = jnp.where(q.main_n > 0, q.m_head, 0)
    tail = head + q.main_n
    m_last = jnp.take(q.m_times, jnp.clip(tail - 1, 0, C - 1))
    can_append = (q.main_n == 0) | (st[0] > m_last)
    can_append = can_append & (tail + S <= C)

    def append(q):
        def put(col, scol):
            return jax.lax.dynamic_update_slice_in_dim(col, scol, tail, 0)

        return q._replace(
            m_times=put(q.m_times, st),
            m_types=put(q.m_types, sty),
            m_args=put(q.m_args, sarg),
            m_seqs=put(q.m_seqs, sseq),
            m_head=head,
        )

    def merge_all(q):
        # Rotate the ring back to physical 0 (masking the dead slots
        # before the head and the stale tail), then counting-merge.
        i_idx = jnp.arange(C, dtype=jnp.int32)
        mt = _ring_unroll(q.m_times, jnp.inf, q.m_head, q.main_n)
        my = _ring_unroll(q.m_types, -1, q.m_head, q.main_n)
        ma = _ring_unroll(q.m_args, 0.0, q.m_head, q.main_n)
        ms = _ring_unroll(q.m_seqs, 2**31 - 1, q.m_head, q.main_n)

        older = jnp.where(
            sev,
            jnp.searchsorted(mt, st, side="left").astype(jnp.int32),
            jnp.searchsorted(mt, st, side="right").astype(jnp.int32),
        )
        older = jnp.minimum(older, q.main_n)
        j_idx = jnp.arange(S, dtype=jnp.int32)
        pos = jnp.where(sval, older + j_idx, C)

        # Positions are unique, so the per-slot insert counts reduce to
        # a scatter-histogram + exclusive cumsum — one linear pass over
        # the output instead of a per-slot binary search.
        counts = jnp.zeros((C,), jnp.int32).at[pos].add(1, mode="drop")
        csum = jnp.cumsum(counts)
        ins_before = (csum - counts).astype(jnp.int32)
        is_ins = counts > 0
        src = jnp.where(
            is_ins, C + jnp.clip(ins_before, 0, S - 1),
            jnp.clip(i_idx - ins_before, 0, C - 1),
        )

        def merge(col, scol):
            return jnp.take(jnp.concatenate([col, scol]), src, axis=0)

        return q._replace(
            m_times=merge(mt, st),
            m_types=merge(my, sty),
            m_args=merge(ma, sarg),
            m_seqs=merge(ms, sseq),
            m_head=jnp.int32(0),
        )

    # When the ring is smaller than the staging block the append path
    # can never fire (and would not even trace) — elide it statically.
    if S <= C:
        q = jax.lax.cond(can_append, append, merge_all, q)
    else:
        q = merge_all(q)
    empty_t, empty_y, empty_a, empty_s = _sentinel_cols(S, q.s_args.shape[1])
    return q._replace(
        s_times=empty_t, s_types=empty_y, s_args=empty_a, s_seqs=empty_s,
        s_evict=jnp.zeros((S,), bool),
        main_n=q.main_n + q.stage_n,
        stage_n=jnp.int32(0),
    )


def _refill_front(q: TieredDeviceQueue) -> TieredDeviceQueue:
    """Refill the front tier from the main array (rare-ish path).

    Staging is flushed first (staged keys may precede the main head),
    after which every main element sorts after every front element, so
    the refill is a plain concatenation: front occupied prefix followed
    by the main head.  The main ring just advances ``m_head`` — an
    O(front_cap) gather, no O(capacity) shift.
    """
    q = jax.lax.cond(q.stage_n > 0, _flush_stage, lambda q: q, q)
    F = q.front_cap
    C = q.capacity
    take = jnp.minimum(F - q.front_n, q.main_n)
    i_idx = jnp.arange(F, dtype=jnp.int32)
    src = jnp.where(
        i_idx < q.front_n, i_idx,
        F + jnp.clip(q.m_head + i_idx - q.front_n, 0, C - 1),
    )
    fill_ok = i_idx < q.front_n + take

    def refill(fcol, mcol, fill):
        out = jnp.take(jnp.concatenate([fcol, mcol]), src, axis=0)
        mask = fill_ok if out.ndim == 1 else fill_ok[:, None]
        return jnp.where(mask, out, fill)

    main_n = q.main_n - take
    return q._replace(
        f_times=refill(q.f_times, q.m_times, jnp.inf),
        f_types=refill(q.f_types, q.m_types, -1),
        f_args=refill(q.f_args, q.m_args, 0.0),
        f_seqs=refill(q.f_seqs, q.m_seqs, 2**31 - 1),
        front_n=q.front_n + take,
        main_n=main_n,
        m_head=jnp.where(main_n > 0, q.m_head + take, 0),
    )


def tiered_queue_extract(q: TieredDeviceQueue, max_len: int, lookaheads,
                         t_cap=None):
    """Window extraction from the front tier (paper Fig 2).

    Identical take rule and output as :func:`device_queue_extract`, but
    the candidate read, prefix mask, and shift-left pop all touch only
    the ``front_cap``-sized front tier — O(front_cap) per batch
    regardless of capacity.  When the front has drained below
    ``max_len`` while later tiers still hold events, a ``lax.cond``
    refills it from the main array first (amortized over
    ``(front_cap - max_len) / max_len`` batches).

    Returns ``(q', ts, tys, args, length)``.
    """
    if max_len > q.front_cap:
        raise ValueError(
            f"max_len {max_len} exceeds front tier capacity {q.front_cap}"
        )
    k = max_len
    F = q.front_cap
    num_types = lookaheads.shape[0]

    need_refill = (q.front_n < k) & ((q.stage_n > 0) | (q.main_n > 0))
    q = jax.lax.cond(need_refill, _refill_front, lambda q: q, q)

    ts_c = q.f_times[:k]
    tys_c = q.f_types[:k]
    valid = tys_c >= 0
    la = lookaheads[jnp.clip(tys_c, 0, num_types - 1)]
    wins = jnp.where(valid, ts_c + la, jnp.inf)
    take = window_prefix_mask(ts_c, wins, valid, t_cap)
    length = jnp.sum(take).astype(jnp.int32)

    ts = jnp.where(take, ts_c, 0.0)
    tys = jnp.where(take, tys_c, 0)
    args = jnp.where(take[:, None], q.f_args[:k], 0.0)

    def shift(col, fill):
        pad = jnp.full((k,) + col.shape[1:], fill, col.dtype)
        return jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([col, pad]), length, F
        )

    q = q._replace(
        f_times=shift(q.f_times, jnp.inf),
        f_types=shift(q.f_types, -1),
        f_args=shift(q.f_args, 0.0),
        f_seqs=shift(q.f_seqs, 2**31 - 1),
        front_n=q.front_n - length,
        size=q.size - length,
    )
    return q, ts, tys, args, length


def _default_fill_accounting(q, rows):
    """Reference seq/overflow rule shared by the tiered fills: valid
    row ``r`` gets ``seq = next_seq + vrank(r)`` and survives iff
    ``size + vrank(r) < capacity`` (``size`` counts ghosts).  Returns
    ``(seq_r, insert, counters)`` for :func:`_tiered_fill_finish`."""
    ty_r = rows[:, 1].astype(jnp.int32)
    valid = ty_r >= 0
    vrank = _prefix_rank(valid)
    num_valid = jnp.sum(valid).astype(jnp.int32)
    insert = valid & (q.size + vrank < q.capacity)
    num_insert = jnp.sum(insert).astype(jnp.int32)
    seq_r = q.next_seq + vrank
    counters = dict(
        size=q.size + num_valid,
        next_seq=q.next_seq + num_valid,
        dropped=q.dropped + (num_valid - num_insert),
    )
    return seq_r, insert, counters


def _tiered_fill_finish(q, rows, b_time, seq_r, insert, counters,
                        kernels: str = "xla", b_seq=None):
    """Shared tail of BOTH tiered fill families (the ROADMAP-flagged
    factoring): partition the emit block against the tier boundary,
    counting-merge the near rows into the sorted front (evicting its
    tail to staging when full — the merge output is ``front_cap + R``
    wide, so nothing is lost), append the rest to the staging ring,
    and install the caller-computed counters.

    Works on :class:`TieredDeviceQueue` and :class:`Tiered3DeviceQueue`
    alike (identical ``f_*``/``s_*`` field names); the two-tier
    ``s_evict`` tags are updated iff the queue carries them.  The
    overflow/seq RULE lives with the caller: ``seq_r`` is the per-row
    seq (default ``next_seq + vrank``; the sharded engine supplies
    globally-assigned seqs) and ``insert`` the per-row survive mask —
    only their consequences are applied here, so the trickiest
    accounting exists exactly once.  Row seqs must exceed every queued
    seq (true for fresh emits under both the local and the global seq
    discipline) — the front-merge tie handling relies on it — UNLESS
    ``b_seq`` is given: then the boundary partition and the front-merge
    placement both compare full ``(time, seq)`` lexicographic keys
    (all-pairs against the front, XLA kernels only), which is what lets
    previously *spilled* rows — whose seqs are older than freshly
    queued ones — reabsorb exactly where they belong
    (:func:`tiered3_queue_absorb_rows`).

    ``kernels="pallas"`` computes the front counting-merge with the
    Pallas kernel (:func:`repro.kernels.queue_front.front_merge`) —
    bit-identical output, VMEM-resident on TPU, interpret mode
    on CPU; the staging appends and counters stay in XLA.
    """
    R = rows.shape[0]
    F = q.front_cap
    t_r = rows[:, 0]
    ty_r = rows[:, 1].astype(jnp.int32)
    arg_r = rows[:, 2:]
    r_idx = jnp.arange(R, dtype=jnp.int32)

    if b_seq is None:
        # Emit seqs all exceed every queued seq, so a timestamp TIE
        # with the boundary already sorts the row after it — the
        # partition is on time alone.
        to_front = insert & (t_r < b_time)
    else:
        # Lex-exact partition for reabsorbed (old-seq) rows.
        to_front = insert & (
            (t_r < b_time) | ((t_r == b_time) & (seq_r < b_seq))
        )
    to_stage = insert & ~to_front

    # --- front merge (output F + R wide: overflow becomes eviction) ---
    FE = F + R
    if kernels == "pallas":
        if b_seq is not None:
            raise ValueError(
                "lex-exact fill (b_seq) is XLA-only; absorb spilled "
                "rows with kernels='xla'"
            )
        from repro.kernels.queue_front import front_merge

        merged_t, merged_y, merged_a, merged_s = front_merge(
            q.f_times, q.f_types, q.f_args, q.f_seqs, q.front_n,
            t_r, ty_r, arg_r, seq_r, to_front,
        )
    else:
        perm = _small_lex_perm(
            jnp.where(to_front, t_r, jnp.inf),
            jnp.where(to_front, seq_r, _I32_MAX),
        )
        rt = jnp.where(to_front, t_r, jnp.inf)[perm]
        rty = ty_r[perm]
        rarg = arg_r[perm]
        rseq = seq_r[perm]
        rins = to_front[perm]

        if b_seq is None:
            # Same strict-total-order shortcut as
            # device_queue_fill_rows: row seqs all exceed queued seqs,
            # so position = searchsorted on time.
            older = jnp.minimum(
                jnp.searchsorted(
                    q.f_times, rt, side="right").astype(jnp.int32),
                q.front_n,
            )
        else:
            # Reabsorbed rows carry OLD seqs: count the occupied front
            # slots strictly lex-before each row (all-pairs, R × F
            # fused bools — boundary-rare, never the per-batch path).
            occ_f = (jnp.arange(F, dtype=jnp.int32) < q.front_n)[None, :]
            lex_lt = (q.f_times[None, :] < rt[:, None]) | (
                (q.f_times[None, :] == rt[:, None])
                & (q.f_seqs[None, :] < rseq[:, None])
            )
            older = jnp.sum(occ_f & lex_lt, axis=1).astype(jnp.int32)
        pos = jnp.where(rins, older + r_idx, FE + R)

        # `pos` ascends over the lex-sorted rows: searchsorted rebuild.
        i_idx = jnp.arange(FE, dtype=jnp.int32)
        ins_before = jnp.searchsorted(
            pos, i_idx, side="left"
        ).astype(jnp.int32)
        is_ins = (
            jnp.searchsorted(pos, i_idx, side="right").astype(jnp.int32)
            > ins_before
        )
        src = jnp.where(
            is_ins, FE + jnp.clip(ins_before, 0, R - 1),
            jnp.clip(i_idx - ins_before, 0, FE - 1),
        )

        def fmerge(col, rcol, fill):
            ext = jnp.concatenate(
                [col, jnp.full((R,) + col.shape[1:], fill, col.dtype),
                 rcol]
            )
            return jnp.take(ext, src, axis=0)

        merged_t = fmerge(q.f_times, rt, jnp.inf)
        merged_y = fmerge(q.f_types, rty, -1)
        merged_a = fmerge(q.f_args, rarg, 0.0)
        merged_s = fmerge(q.f_seqs, rseq, 2**31 - 1)

    n_front = jnp.sum(to_front).astype(jnp.int32)
    occ_after = q.front_n + n_front
    evict_cnt = jnp.maximum(occ_after - F, 0)
    front_n_new = jnp.minimum(occ_after, F)

    # --- staging appends: evicted front tail, then direct rows --------
    SC = q.stage_cap
    e_valid = merged_y[F:] >= 0
    dest_e = jnp.where(e_valid, q.stage_n + r_idx, SC)
    srank = _prefix_rank(to_stage)
    dest_s = jnp.where(to_stage, q.stage_n + evict_cnt + srank, SC)
    n_stage = jnp.sum(to_stage).astype(jnp.int32)

    def stage_put(col, evals, svals):
        col = col.at[dest_e].set(evals, mode="drop")
        return col.at[dest_s].set(svals, mode="drop")

    extra = {}
    if hasattr(q, "s_evict"):
        s_evict = q.s_evict.at[dest_e].set(True, mode="drop")
        extra["s_evict"] = s_evict.at[dest_s].set(False, mode="drop")

    return q._replace(
        f_times=merged_t[:F], f_types=merged_y[:F],
        f_args=merged_a[:F], f_seqs=merged_s[:F],
        s_times=stage_put(q.s_times, merged_t[F:], t_r),
        s_types=stage_put(q.s_types, merged_y[F:], ty_r),
        s_args=stage_put(q.s_args, merged_a[F:], arg_r),
        s_seqs=stage_put(q.s_seqs, merged_s[F:], seq_r),
        front_n=front_n_new,
        stage_n=q.stage_n + evict_cnt + n_stage,
        **counters,
        **extra,
    )


def tiered_queue_fill_rows(q: TieredDeviceQueue, rows) -> TieredDeviceQueue:
    """Per-batch emit insert touching only the front and staging tiers.

    Row layout is ``(time, type, arg...)``; ``type < 0`` rows are
    skipped.  Valid row ``r`` receives ``seq = next_seq + r`` and is
    dropped iff ``size + r >= capacity`` — bit-exact reference overflow
    accounting (``size`` counts ghosts).  Surviving rows whose timestamp
    precedes the tier boundary (the earliest key in staging ∪ main) are
    counting-merged into the sorted front at O(front_cap · R) fused
    bools + O(front_cap) gathers; rows at or past the boundary append to
    the staging ring (:func:`_tiered_fill_finish`, shared with the
    tiered3 fills).  A staging ring that could overflow on this batch
    is first bulk-merged into the main array via the rare
    :func:`_flush_stage` path.

    No O(capacity) work on the common path — this is what makes
    per-batch scheduling cost independent of queue capacity.
    """
    rows = jnp.asarray(rows, jnp.float32)
    R = rows.shape[0]
    C = q.capacity
    if R > q.stage_cap:
        raise ValueError(
            f"emit block of {R} rows exceeds stage_cap {q.stage_cap}"
        )

    # Staging must absorb up to R appends this batch (direct + evicted).
    q = jax.lax.cond(
        q.stage_n + R > q.stage_cap, _flush_stage, lambda q: q, q
    )

    seq_r, insert, counters = _default_fill_accounting(q, rows)

    # Tier boundary: earliest key outside the front.  The main head is
    # read at the ring offset (slots before m_head are dead and must
    # not leak into the boundary).
    m_min = jnp.where(
        q.main_n > 0,
        jnp.take(q.m_times, jnp.clip(q.m_head, 0, C - 1)),
        jnp.inf,
    )
    b_time = jnp.minimum(m_min, jnp.min(q.s_times))
    return _tiered_fill_finish(q, rows, b_time, seq_r, insert, counters)


def tiered_queue_to_flat(q: TieredDeviceQueue) -> DeviceQueue:
    """Canonical flat view of a tiered queue (host-side, for tests).

    Gathers the occupied slots of all three tiers, sorts by
    ``(time, seq)``, and lays them out as a canonical
    :class:`DeviceQueue` with identical logical counters — the flat and
    reference ops' view of the same pending set.
    """
    head, main_n = int(q.m_head), int(q.main_n)
    cols = []
    for pre in ("f", "m", "s"):
        cols.append(tuple(
            np.asarray(getattr(q, f"{pre}_{name}"))
            for name in ("times", "types", "args", "seqs")
        ))
    # Only the live window of the main ring — slots outside
    # [m_head, m_head + main_n) are dead (stale values, not sentinels).
    cols[1] = tuple(c[head:head + main_n] for c in cols[1])
    times = np.concatenate([c[0] for c in cols])
    types = np.concatenate([c[1] for c in cols])
    args = np.concatenate([c[2] for c in cols])
    seqs = np.concatenate([c[3] for c in cols])
    occ = types >= 0
    order = np.lexsort((seqs[occ], times[occ]))
    n = int(occ.sum())
    C = q.capacity
    assert n <= C, "tier occupancy exceeded logical capacity"
    out_t = np.full((C,), np.inf, np.float32)
    out_y = np.full((C,), -1, np.int32)
    out_a = np.zeros((C, q.f_args.shape[1]), np.float32)
    out_s = np.full((C,), 2**31 - 1, np.int32)
    out_t[:n] = times[occ][order]
    out_y[:n] = types[occ][order]
    out_a[:n] = args[occ][order]
    out_s[:n] = seqs[occ][order]
    return DeviceQueue(
        times=jnp.asarray(out_t), types=jnp.asarray(out_y),
        args=jnp.asarray(out_a), seqs=jnp.asarray(out_s),
        size=jnp.asarray(q.size), next_seq=jnp.asarray(q.next_seq),
        dropped=jnp.asarray(q.dropped),
    )


# ---------------------------------------------------------------------------
# Three-tier queue: front / staging / sorted-run log / main (DESIGN.md §4.4)
# ---------------------------------------------------------------------------

def _lex_order(ts, sq):
    """Ascending ``(time, seq)`` permutation for a mid-size vector.

    ONE ``lax.sort`` call with two key operands (lexicographic) and an
    iota payload, instead of the all-pairs rank of
    :func:`_small_lex_perm`: the run-merge vectors are a few thousand
    elements, where m² fused bools stop being free, and XLA:CPU sort
    custom calls have enough fixed overhead that one variadic call
    beats two chained argsorts.
    """
    idx = jnp.arange(ts.shape[0], dtype=jnp.int32)
    _, _, perm = jax.lax.sort((ts, sq, idx), num_keys=2)
    return perm


class Tiered3DeviceQueue(NamedTuple):
    """Pending-event set split into front / staging / run log / main.

    Same front (``f_*``) and staging (``s_*``) tiers as
    :class:`TieredDeviceQueue`; the differences are the third tier and
    the slack reserve:

    * ``r_*`` — the **run log**: ``num_runs`` fixed-size sorted runs of
      ``stage_cap`` slots each.  A staging flush becomes one new run
      (sorted by true ``(time, seq)``); ``r_off``/``r_len`` bound each
      run's live remainder (``r_off`` advances as refills consume the
      run head, so nothing is ever "put back").  The per-run min-time
      summary is ``r_times[i, r_off[i]]``.
    * ``m_*`` — the **main** head-offset ring, physically
      ``capacity + num_runs * stage_cap`` slots: the extra slack lets
      an exhausted run pool merge into main as one bounded tail append
      when the whole pool lies past the main tail; otherwise the pool
      and the ring merge in O(capacity) linear passes, with no sort
      (:func:`_merge_block_into_ring`).

    Because every element's true ``seq`` participates in the run and
    refill merges, no eviction tags are needed: lexicographic
    ``(time, seq)`` order is recovered exactly wherever tiers meet.
    Tier invariant and accounting match :class:`TieredDeviceQueue`:
    ``max(front) <= min(staging ∪ runs ∪ main)``, and the *logical*
    capacity excludes the slack — ``capacity`` is what overflow
    accounting is measured against, bit-identical to the reference.

    Front-tier hot loops come in two implementations selected by the
    ``kernels=`` argument of :func:`tiered3_queue_extract` /
    :func:`tiered3_queue_fill_rows` (surfaced as
    ``DeviceEngine(queue_kernels=...)``): ``"xla"`` — the
    all-pairs-rank + gather shapes tuned for XLA:CPU — or ``"pallas"``
    — :mod:`repro.kernels.queue_front` kernels that keep the window
    extract and the front counting-merge in VMEM on TPU (interpret
    mode elsewhere, bit-identical output).  The queue layout itself is
    implementation-agnostic, which is why the knob rides on the
    functions, not in the pytree.
    """

    f_times: jnp.ndarray   # f32[front_cap]
    f_types: jnp.ndarray   # i32[front_cap], -1 = empty
    f_args: jnp.ndarray    # f32[front_cap, ARG_WIDTH]
    f_seqs: jnp.ndarray    # i32[front_cap]
    m_times: jnp.ndarray   # f32[capacity + num_runs*stage_cap]
    m_types: jnp.ndarray   # i32[...]
    m_args: jnp.ndarray    # f32[..., ARG_WIDTH]
    m_seqs: jnp.ndarray    # i32[...]
    s_times: jnp.ndarray   # f32[stage_cap]
    s_types: jnp.ndarray   # i32[stage_cap]
    s_args: jnp.ndarray    # f32[stage_cap, ARG_WIDTH]
    s_seqs: jnp.ndarray    # i32[stage_cap]
    r_times: jnp.ndarray   # f32[num_runs, stage_cap]
    r_types: jnp.ndarray   # i32[num_runs, stage_cap]
    r_args: jnp.ndarray    # f32[num_runs, stage_cap, ARG_WIDTH]
    r_seqs: jnp.ndarray    # i32[num_runs, stage_cap]
    r_off: jnp.ndarray     # i32[num_runs], consumed prefix of each run
    r_len: jnp.ndarray     # i32[num_runs], written length of each run
    front_n: jnp.ndarray   # i32 scalar
    main_n: jnp.ndarray    # i32 scalar
    m_head: jnp.ndarray    # i32 scalar, first logical main slot (ring)
    stage_n: jnp.ndarray   # i32 scalar
    size: jnp.ndarray      # i32 scalar, logical pushes (incl. ghosts)
    next_seq: jnp.ndarray  # i32 scalar
    dropped: jnp.ndarray   # i32 scalar

    @property
    def main_phys(self) -> int:
        return self.m_times.shape[0]

    @property
    def capacity(self) -> int:
        return self.main_phys - self.num_runs * self.stage_cap

    @property
    def front_cap(self) -> int:
        return self.f_times.shape[0]

    @property
    def stage_cap(self) -> int:
        return self.s_times.shape[0]

    @property
    def num_runs(self) -> int:
        return self.r_times.shape[0]


def tiered3_queue_init(capacity: int, *, front_cap: int = 256,
                       stage_cap: int = 256, num_runs: int = 8,
                       arg_width: int = ARG_WIDTH) -> Tiered3DeviceQueue:
    front_cap = min(front_cap, capacity)
    phys = capacity + num_runs * stage_cap
    ft, fy, fa, fs = _sentinel_cols(front_cap, arg_width)
    mt, my, ma, ms = _sentinel_cols(phys, arg_width)
    st, sy, sa, ss = _sentinel_cols(stage_cap, arg_width)
    z = jnp.int32(0)
    return Tiered3DeviceQueue(
        f_times=ft, f_types=fy, f_args=fa, f_seqs=fs,
        m_times=mt, m_types=my, m_args=ma, m_seqs=ms,
        s_times=st, s_types=sy, s_args=sa, s_seqs=ss,
        r_times=jnp.full((num_runs, stage_cap), jnp.inf, jnp.float32),
        r_types=jnp.full((num_runs, stage_cap), -1, jnp.int32),
        r_args=jnp.zeros((num_runs, stage_cap, arg_width), jnp.float32),
        r_seqs=jnp.full((num_runs, stage_cap), 2**31 - 1, jnp.int32),
        r_off=jnp.zeros((num_runs,), jnp.int32),
        r_len=jnp.zeros((num_runs,), jnp.int32),
        front_n=z, main_n=z, m_head=z, stage_n=z, size=z, next_seq=z,
        dropped=z,
    )


def tiered3_queue_from_host(events, capacity: int, *, front_cap: int = 256,
                            stage_cap: int = 256, num_runs: int = 8,
                            arg_width: int = ARG_WIDTH, seqs=None
                            ) -> Tiered3DeviceQueue:
    """Host-built seed queue, one device_put (cf. tiered_queue_from_host).

    Earliest ``front_cap`` events seed the front, the rest the main
    array at head 0; runs and staging start empty.  Reference overflow
    semantics against the LOGICAL capacity (the slack is structure).

    ``seqs`` optionally supplies explicit global seqs (shard seeding):
    the events must then fit ``capacity`` (the global overflow rule was
    applied upstream) and the counters become shard-local — ``size`` =
    occupancy, ``dropped`` = 0, ``next_seq`` past the largest seq.
    """
    front_cap = min(front_cap, capacity)
    phys = capacity + num_runs * stage_cap
    times, types, args, seq_col, n, m = _host_sorted_seed(
        events, capacity, arg_width, seqs
    )
    nf = min(m, front_cap)
    ft = np.full((front_cap,), np.inf, np.float32)
    fy = np.full((front_cap,), -1, np.int32)
    fa = np.zeros((front_cap, arg_width), np.float32)
    fs = np.full((front_cap,), 2**31 - 1, np.int32)
    ft[:nf], fy[:nf], fa[:nf], fs[:nf] = (
        times[:nf], types[:nf], args[:nf], seq_col[:nf]
    )
    mt = np.full((phys,), np.inf, np.float32)
    my = np.full((phys,), -1, np.int32)
    ma = np.zeros((phys, arg_width), np.float32)
    ms = np.full((phys,), 2**31 - 1, np.int32)
    nm = m - nf
    mt[:nm], my[:nm], ma[:nm], ms[:nm] = (
        times[nf:], types[nf:], args[nf:], seq_col[nf:]
    )
    if seqs is None:
        size, next_seq, dropped = n, n, n - m
    else:
        size = m
        next_seq = int(seq_col.max()) + 1 if m else 0
        dropped = 0
    st, sy, sa, ss = (np.full((stage_cap,), np.inf, np.float32),
                      np.full((stage_cap,), -1, np.int32),
                      np.zeros((stage_cap, arg_width), np.float32),
                      np.full((stage_cap,), 2**31 - 1, np.int32))
    return jax.device_put(Tiered3DeviceQueue(
        f_times=ft, f_types=fy, f_args=fa, f_seqs=fs,
        m_times=mt, m_types=my, m_args=ma, m_seqs=ms,
        s_times=st, s_types=sy, s_args=sa, s_seqs=ss,
        r_times=np.full((num_runs, stage_cap), np.inf, np.float32),
        r_types=np.full((num_runs, stage_cap), -1, np.int32),
        r_args=np.zeros((num_runs, stage_cap, arg_width), np.float32),
        r_seqs=np.full((num_runs, stage_cap), 2**31 - 1, np.int32),
        r_off=np.zeros((num_runs,), np.int32),
        r_len=np.zeros((num_runs,), np.int32),
        front_n=np.int32(nf), main_n=np.int32(nm), m_head=np.int32(0),
        stage_n=np.int32(0),
        size=np.int32(size), next_seq=np.int32(next_seq),
        dropped=np.int32(dropped),
    ))


def _run_mins(q: Tiered3DeviceQueue):
    """Per-run min-time summary: the head of each live remainder
    (``inf`` for consumed/empty runs).  One O(num_runs) gather."""
    S = q.stage_cap
    head = jnp.take_along_axis(
        q.r_times, jnp.clip(q.r_off, 0, S - 1)[:, None], axis=1
    )[:, 0]
    return jnp.where(q.r_len > q.r_off, head, jnp.inf)


def tiered3_queue_has_pending(q: Tiered3DeviceQueue):
    """True while any tier holds a real event (O(num_runs))."""
    return ((q.front_n > 0) | (q.stage_n > 0) | (q.main_n > 0)
            | jnp.any(q.r_len > q.r_off))


def tiered3_queue_occupancy(q: Tiered3DeviceQueue):
    """Number of real pending events across all four tiers."""
    return (q.front_n + q.stage_n + q.main_n
            + jnp.sum(q.r_len - q.r_off).astype(jnp.int32))


def tiered3_queue_next_time(q: Tiered3DeviceQueue):
    """Earliest pending timestamp (``inf`` when empty); O(stage_cap +
    num_runs) on the drained-front fallback, capacity-independent."""
    m_min = jnp.where(
        q.main_n > 0,
        jnp.take(q.m_times, jnp.clip(q.m_head, 0, q.main_phys - 1)),
        _INF,
    )
    rest = jnp.minimum(
        jnp.minimum(jnp.min(q.s_times), jnp.min(_run_mins(q))), m_min
    )
    return jnp.where(q.front_n > 0, q.f_times[0], rest)


def _ring_rank(q: Tiered3DeviceQueue, bt, bs):
    """Lex rank of each ``(bt, bs)`` key among the ``main_n`` live main
    elements: how many of them sort strictly before it.  A vectorised
    binary search over the logical ring (physical slot
    ``(m_head + i) % P``) — ``ceil(log2(capacity + 1))`` rounds, enough
    for any ``main_n <= capacity``, of one small gather per column."""
    P = q.main_phys
    lo = jnp.zeros(bt.shape, jnp.int32)
    hi = jnp.full(bt.shape, q.main_n, jnp.int32)
    for _ in range(q.capacity.bit_length()):
        mid = (lo + hi) // 2
        slot = (q.m_head + mid) % P
        mt = q.m_times[slot]
        ms = q.m_seqs[slot]
        before = (mt < bt) | ((mt == bt) & (ms < bs))
        active = lo < hi
        lo = jnp.where(active & before, mid + 1, lo)
        hi = jnp.where(active & ~before, mid, hi)
    return lo


def _shift_down(col, d: int, fill):
    """``col`` moved ``d`` slots toward the end (static ``d``), the
    first ``d`` slots set to ``fill``; what passes the end is lost."""
    pad = jnp.full((d,) + col.shape[1:], fill, col.dtype)
    return jnp.concatenate([pad, col[:-d]])


def _merge_block_into_ring(q: Tiered3DeviceQueue, run_live, bt, by, ba, bs):
    """Linear-time merge of the ring's live window with a sorted block,
    written at physical head 0 — exactly the ``(time, seq)`` sort of
    ring ∪ block, with no sort and no ring-long gather.

    Block element ``j`` (``j < run_live``) lands at ``j + rank_j``
    (:func:`_ring_rank`); main element ``i`` at ``i + c_i``, where
    ``c_i`` counts the block elements before it.  The ring is unrolled
    by one ``dynamic_slice`` of the doubled column, each main element
    moves forward by ``c_i`` in ``ceil(log2(RL + 1))`` static
    shift-and-select passes, highest bit first, and the block rows go
    into the holes with one ``RL``-row scatter.  After the passes for
    the bits above ``k`` an element sits at ``i + (c_i`` with bits below
    ``k`` cleared); that is strictly increasing in ``i`` because ``c`` is
    non-decreasing, so no pass ever moves two elements onto one slot.
    """
    P = q.main_phys
    RL = bt.shape[0]
    j_idx = jnp.arange(RL, dtype=jnp.int32)
    rank = _ring_rank(q, bt, bs)
    b_live = j_idx < run_live
    # rank <= main_n <= capacity < P, so no live rank is dropped.
    counts = jnp.zeros((P,), jnp.int32).at[
        jnp.where(b_live, rank, P)].add(1, mode="drop")
    shift = jnp.cumsum(counts)

    def rows(mask, col):
        return mask if col.ndim == 1 else mask[:, None]

    # The live window moved to physical 0: slot i holds logical i.
    occ = jnp.arange(P) < q.main_n
    fills = (jnp.inf, -1, 0.0, 2**31 - 1)
    cols = [
        jnp.where(rows(occ, c), jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([c, c]), q.m_head, P, 0), f)
        for c, f in zip((q.m_times, q.m_types, q.m_args, q.m_seqs), fills)
    ]
    for k in reversed(range(RL.bit_length())):
        d = 1 << k
        move = occ & ((shift & d) != 0)
        land = _shift_down(move, d, False)
        occ = land | (occ & ~move)
        shift = jnp.where(land, _shift_down(shift, d, 0), shift)
        cols = [jnp.where(rows(land, c), _shift_down(c, d, f), c)
                for c, f in zip(cols, fills)]
    # Dead rows get distinct slots past the end, which the scatter drops.
    dest = jnp.where(b_live, j_idx + rank, P + j_idx)
    mt, my, ma, ms = (
        jnp.where(rows(occ, c), c, f).at[dest].set(
            b, mode="drop", indices_are_sorted=True, unique_indices=True)
        for c, f, b in zip(cols, fills, (bt, by, ba, bs)))
    return q._replace(m_times=mt, m_types=my, m_args=ma, m_seqs=ms,
                      m_head=jnp.int32(0))


@jax.named_scope(MERGE)
def _merge_runs_into_main(q: Tiered3DeviceQueue) -> Tiered3DeviceQueue:
    """Drain the whole run pool into the main ring (rare path).

    The live remainders of every run are lex-sorted by their true
    ``(time, seq)`` keys into one block (O(num_runs · stage_cap ·
    log) — bounded, capacity-independent).  Fast path: when the block's
    minimum strictly exceeds the main tail and the ring's physical
    slack still fits it, ONE tail ``dynamic_update_slice`` lands it.
    Fallback (the only O(capacity) operation in the tiered3 family):
    merge the sorted ring and the sorted block in linear time into a
    ring at physical head 0 (:func:`_merge_block_into_ring`) —
    amortized over an entire pool (``num_runs × stage_cap`` staged
    events) per firing.  Never drops: occupancy <= logical capacity <=
    physical size.
    """
    R, S, P = q.num_runs, q.stage_cap, q.main_phys
    RL = R * S
    k_idx = jnp.arange(S, dtype=jnp.int32)[None, :]
    live = (k_idx >= q.r_off[:, None]) & (k_idx < q.r_len[:, None])
    bt = jnp.where(live, q.r_times, jnp.inf).reshape(RL)
    by = jnp.where(live, q.r_types, -1).reshape(RL)
    ba = jnp.where(live[:, :, None], q.r_args, 0.0).reshape(
        RL, q.r_args.shape[2])
    bs = jnp.where(live, q.r_seqs, _I32_MAX).reshape(RL)
    order = _lex_order(bt, bs)
    bt, by, ba, bs = bt[order], by[order], ba[order], bs[order]
    run_live = jnp.sum(live).astype(jnp.int32)

    head = jnp.where(q.main_n > 0, q.m_head, 0)
    tail = head + q.main_n
    m_last = jnp.take(q.m_times, jnp.clip(tail - 1, 0, P - 1))
    can_append = ((q.main_n == 0) | (bt[0] > m_last)) & (tail + RL <= P)

    def append(q):
        def put(col, bcol):
            return jax.lax.dynamic_update_slice_in_dim(col, bcol, tail, 0)

        return q._replace(
            m_times=put(q.m_times, bt),
            m_types=put(q.m_types, by),
            m_args=put(q.m_args, ba),
            m_seqs=put(q.m_seqs, bs),
            m_head=head,
        )

    q = jax.lax.cond(
        can_append, append,
        lambda q: _merge_block_into_ring(q, run_live, bt, by, ba, bs), q)
    return q._replace(
        main_n=q.main_n + run_live,
        r_off=jnp.zeros((R,), jnp.int32),
        r_len=jnp.zeros((R,), jnp.int32),
    )


@jax.named_scope(MERGE)
def _rotate_main(q: Tiered3DeviceQueue) -> Tiered3DeviceQueue:
    """Re-center the sorted main ring — one O(P) gather, no sort.

    The live window moves to start at a margin of up to ``2·stage_cap``
    dead slots, reclaiming BOTH kinds of headroom at once: tail slack
    for far-future appends and head slack for the bounded near-head
    merge (which writes at ``m_head - n_pre``).  Head slack otherwise
    only accrues as refills consume the head — and a front kept full
    by near-head merges never refills, so the flush must be able to
    mint its own headroom.  Amortized over ~stage_cap-many flush
    events per firing.
    """
    P = q.main_phys
    S = q.stage_cap
    # Generous margin (up to a quarter of the ring): head merges can
    # consume ~stage_cap headroom per flush, and each rotate is O(P),
    # so rotating rarely beats rotating tightly.
    margin = jnp.minimum(jnp.maximum(2 * S, P // 4),
                         jnp.maximum(P - q.main_n - S, 0))
    return q._replace(
        m_times=_ring_unroll(q.m_times, jnp.inf, q.m_head, q.main_n,
                             margin),
        m_types=_ring_unroll(q.m_types, -1, q.m_head, q.main_n, margin),
        m_args=_ring_unroll(q.m_args, 0.0, q.m_head, q.main_n, margin),
        m_seqs=_ring_unroll(q.m_seqs, 2**31 - 1, q.m_head, q.main_n,
                            margin),
        m_head=margin,
    )


def _flush_stage_to_run(q: Tiered3DeviceQueue) -> Tiered3DeviceQueue:
    """Drain the staging ring by SPLITTING the sorted block three ways.

    The staged block is lex-sorted once (O(stage_cap²) fused bools),
    then partitioned by where its elements land relative to the main
    ring — real emit mixes contain both near-head re-emits and
    far-future events, so a single-destination flush would almost
    always hit a fallback:

    * **suffix** (times strictly after the main tail): one O(stage_cap)
      gather + ``dynamic_update_slice`` into the ring's physical
      slack — the common far-future path.  When the tail would run off
      the physical end, the sorted ring is first re-centered
      (:func:`_rotate_main` — one O(P) gather, no sort, amortized
      over the whole slack).
    * **prefix** (times strictly before the K-th element past the
      head): counting-merged with the K+stage_cap head window and
      written back as ONE block starting at ``m_head - n_pre`` — the
      already-consumed ring slots are the headroom (re-minted by the
      same re-centering rotate when they run out).  Beyond the write
      range the merged sequence is the old window shifted by exactly
      ``n_pre``, so slot ``head - n_pre + j`` holds element
      ``head + j - n_pre`` either way: nothing past the window is
      touched.  All-pairs strict lex compares on true ``(time, seq)``
      keys — exact, bounded, no sort custom call.  This is the shape
      that made the two-tier flush an O(capacity) lex merge + ring
      compaction.
    * **middle** (neither, or the prefix guard failed): one new sorted
      run in the log (an O(stage_cap) row write).  When it needs a
      slot and every run is occupied, the pool first drains into main
      (:func:`_merge_runs_into_main`), which frees all of them.

    Every leg builds its block with gathers and lands it with one
    ``dynamic_update_slice`` — XLA:CPU executes those as bulk copies,
    where equivalent scatters cost ~100× more per row.  Every leg is
    O(stage_cap·K) worst case — capacity-independent.
    """
    S = q.stage_cap
    P = q.main_phys
    # Head window: K main elements is how far past the head a "near"
    # emit may land and still take the bounded merge (wider blocks use
    # the run log).  A quarter of the stage keeps the all-pairs compare
    # small while covering the emits-just-past-the-window DES shape.
    K = max(min(S, 32), S // 4)
    KS = K + S
    perm = _small_lex_perm(q.s_times, q.s_seqs)
    st = q.s_times[perm]
    sty = q.s_types[perm]
    sarg = q.s_args[perm]
    sseq = q.s_seqs[perm]
    sval = sty >= 0
    s_total = q.stage_n
    j_idx = jnp.arange(S, dtype=jnp.int32)

    def sub_block(offset, count):
        """Sorted sub-range [offset, offset+count) of the staged block
        as its own S-wide block (sentinels past ``count``)."""
        idx = jnp.clip(offset + j_idx, 0, S - 1)
        live = j_idx < count
        return (
            jnp.where(live, st[idx], jnp.inf),
            jnp.where(live, sty[idx], -1),
            jnp.where(live[:, None], sarg[idx], 0.0),
            jnp.where(live, sseq[idx], _I32_MAX),
        )

    # --- suffix: strictly after the main tail -> slack append ---------
    # main_n <= capacity = P - num_runs*S, so after a rotate there is
    # ALWAYS tail room for a stage_cap block.
    head0 = jnp.where(q.main_n > 0, q.m_head, 0)
    m_last = jnp.take(
        q.m_times, jnp.clip(head0 + q.main_n - 1, 0, P - 1))
    after_tail = sval & ((q.main_n == 0) | (st > m_last))
    n_suf = jnp.sum(after_tail).astype(jnp.int32)

    def append_suffix(q):
        q = jax.lax.cond(
            jnp.where(q.main_n > 0, q.m_head, 0) + q.main_n + S > P,
            _rotate_main, lambda q: q, q,
        )
        head1 = jnp.where(q.main_n > 0, q.m_head, 0)
        tail1 = head1 + q.main_n
        bt, by, ba, bs = sub_block(s_total - n_suf, n_suf)

        def put(col, bcol):
            return jax.lax.dynamic_update_slice_in_dim(col, bcol, tail1, 0)

        return q._replace(
            m_times=put(q.m_times, bt),
            m_types=put(q.m_types, by),
            m_args=put(q.m_args, ba),
            m_seqs=put(q.m_seqs, bs),
            m_head=head1,
            main_n=q.main_n + n_suf,
        )

    q = jax.lax.cond(n_suf > 0, append_suffix, lambda q: q, q)

    # --- prefix: strictly inside the head window -> bounded merge -----
    # (reads the post-suffix state: with a short main the window can
    # include just-appended elements; statically elided when the
    # window cannot even fit the ring — tiny-geometry configs, which
    # the run log covers)
    suf_lo = s_total - n_suf
    n_pre = jnp.int32(0)
    head = jnp.where(q.main_n > 0, q.m_head, 0)
    if KS <= P:
        ext_idx = jnp.clip(head + jnp.arange(KS, dtype=jnp.int32), 0, P - 1)
        ext_live = jnp.arange(KS) < q.main_n
        wt = jnp.where(ext_live, q.m_times[ext_idx], jnp.inf)
        ws = jnp.where(ext_live, q.m_seqs[ext_idx], _I32_MAX)
        wy = jnp.where(ext_live, q.m_types[ext_idx], -1)
        wa = jnp.where(ext_live[:, None], q.m_args[ext_idx], 0.0)
        n_pre_want = jnp.sum(
            sval & (j_idx < suf_lo) & (st < wt[K])
        ).astype(jnp.int32)
        # Without head-side headroom (or a window running off the physical
        # end), re-center the ring: rotation moves positions, not values,
        # so the window columns read above stay valid.
        q = jax.lax.cond(
            (n_pre_want > 0)
            & ((head < n_pre_want) | (head - n_pre_want + KS > P)),
            _rotate_main, lambda q: q, q,
        )
        head = jnp.where(q.main_n > 0, q.m_head, 0)
        # Guard again: degenerate geometries (margin clamped below n_pre)
        # still fall through to the run log.
        n_pre = jnp.where(
            (head >= n_pre_want) & (head - n_pre_want + KS <= P),
            n_pre_want, 0)

        def head_merge(q):
            # Counting merge of the prefix (first n_pre sorted entries)
            # with the sorted window: the B-positions come from all-pairs
            # strict lex compares (exact on true (time, seq) keys), the
            # output block from one searchsorted-driven gather per column.
            is_pre = j_idx < n_pre
            bt = jnp.where(is_pre, st, jnp.inf)
            bs = jnp.where(is_pre, sseq, _I32_MAX)
            w_lt_b = (wt[None, :] < bt[:, None]) | (
                (wt[None, :] == bt[:, None]) & (ws[None, :] < bs[:, None])
            )
            # pos_b ascends (B sorted); invalid rows push past the block.
            pos_b = jnp.where(
                is_pre,
                j_idx + jnp.sum(w_lt_b, axis=1).astype(jnp.int32),
                KS + S,
            )
            i_idx = jnp.arange(KS, dtype=jnp.int32)
            ins_before = jnp.searchsorted(
                pos_b, i_idx, side="right").astype(jnp.int32)
            is_ins = ins_before > jnp.searchsorted(
                pos_b, i_idx, side="left").astype(jnp.int32)
            src = jnp.where(
                is_ins, KS + jnp.clip(ins_before - 1, 0, S - 1),
                jnp.clip(i_idx - ins_before, 0, KS - 1),
            )
            start = head - n_pre

            def merge_put(col, wcol, bcol):
                merged = jnp.take(jnp.concatenate([wcol, bcol]), src, axis=0)
                return jax.lax.dynamic_update_slice_in_dim(
                    col, merged, start, 0)

            return q._replace(
                m_times=merge_put(q.m_times, wt, st),
                m_types=merge_put(q.m_types, wy, sty),
                m_args=merge_put(q.m_args, wa, sarg),
                m_seqs=merge_put(q.m_seqs, ws, sseq),
                m_head=start,
                main_n=q.main_n + n_pre,
            )

        q = jax.lax.cond(n_pre > 0, head_merge, lambda q: q, q)

    # --- middle: whatever neither leg could place -> one sorted run ---
    n_mid = s_total - n_suf - n_pre

    def to_run(q):
        q = jax.lax.cond(
            jnp.all(q.r_len > q.r_off), _merge_runs_into_main,
            lambda q: q, q,
        )
        slot = jnp.argmax(q.r_off >= q.r_len)  # first free run
        bt, by, ba, bs = sub_block(n_pre, n_mid)
        return q._replace(
            r_times=q.r_times.at[slot].set(bt),
            r_types=q.r_types.at[slot].set(by),
            r_args=q.r_args.at[slot].set(ba),
            r_seqs=q.r_seqs.at[slot].set(bs),
            r_off=q.r_off.at[slot].set(0),
            r_len=q.r_len.at[slot].set(n_mid),
        )

    q = jax.lax.cond(n_mid > 0, to_run, lambda q: q, q)

    empty_t, empty_y, empty_a, empty_s = _sentinel_cols(
        S, q.s_args.shape[1])
    return q._replace(
        s_times=empty_t, s_types=empty_y, s_args=empty_a, s_seqs=empty_s,
        stage_n=jnp.int32(0),
    )



def _runs_intersect_refill(q: Tiered3DeviceQueue):
    """True iff some run holds an element the next MAIN-ONLY refill
    would need: the main-only path takes the next
    ``min(front_cap - front_n, main_n)`` main elements, so a run
    matters only if its min key could precede the last of those.  A
    dormant far-future run (e.g. stragglers parked during warmup)
    then costs nothing: refills keep streaming from main and the run
    is consulted again only once the clock reaches it.  Strict time
    comparison — a tie falls back to the exact k-way merge.
    """
    take = jnp.minimum(q.front_cap - q.front_n, q.main_n)
    last_idx = jnp.clip(q.m_head + take - 1, 0, q.main_phys - 1)
    last_t = jnp.take(q.m_times, last_idx)
    # Empty main (take == 0) must still drain live runs.
    return jnp.min(_run_mins(q)) <= jnp.where(take > 0, last_t, jnp.inf)


def _refill_front3_windowed(w: int):
    """Front refill — always bounded, never O(capacity).

    Staging is flushed first (append / head merge / run).  With no run
    intersecting the take, the refill is the two-tier O(front_cap)
    main-head gather (:func:`_refill_main_only`); otherwise the
    bounded k-way merge (:func:`_refill_kway`) with its take capped at
    the static ``w`` — see there for why small top-ups win.
    """
    def refill(q):
        q = jax.lax.cond(
            q.stage_n > 0, _flush_stage_to_run, lambda q: q, q)
        return jax.lax.cond(
            _runs_intersect_refill(q),
            lambda q: _refill_kway(q, w), _refill_main_only, q,
        )

    return refill


def _refill_main_only(q: Tiered3DeviceQueue) -> Tiered3DeviceQueue:
    """Refill with an empty run pool (the common case once far-future
    flushes append straight to main): every main element sorts after
    every front element, so the refill is the two-tier O(front_cap)
    gather — no sort at all.  The main ring just advances ``m_head``.
    """
    F = q.front_cap
    P = q.main_phys
    take = jnp.minimum(F - q.front_n, q.main_n)
    i_idx = jnp.arange(F, dtype=jnp.int32)
    src = jnp.where(
        i_idx < q.front_n, i_idx,
        F + jnp.clip(q.m_head + i_idx - q.front_n, 0, P - 1),
    )
    fill_ok = i_idx < q.front_n + take

    def refill(fcol, mcol, fill):
        out = jnp.take(jnp.concatenate([fcol, mcol]), src, axis=0)
        mask = fill_ok if out.ndim == 1 else fill_ok[:, None]
        return jnp.where(mask, out, fill)

    main_n = q.main_n - take
    return q._replace(
        f_times=refill(q.f_times, q.m_times, jnp.inf),
        f_types=refill(q.f_types, q.m_types, -1),
        f_args=refill(q.f_args, q.m_args, 0.0),
        f_seqs=refill(q.f_seqs, q.m_seqs, 2**31 - 1),
        front_n=q.front_n + take,
        main_n=main_n,
        m_head=jnp.where(main_n > 0, q.m_head + take, 0),
    )


def _refill_kway(q: Tiered3DeviceQueue, w: int | None = None
                 ) -> Tiered3DeviceQueue:
    """Refill against a live run pool: the bounded k-way merge.

    The candidate set is the first ``w`` live elements of every run
    plus the main head window — (num_runs + 1) · w candidates,
    lex-ordered by their true ``(time, seq)`` keys with the all-pairs
    rank (fused bools; an XLA:CPU sort custom call would cost more
    than the whole merge).  The earliest ``min(front_cap - front_n,
    w)`` fill the front; each source just advances its head offset by
    the number taken (runs: ``r_off``; main: ``m_head``), so nothing
    is written back.  Any element outside a candidate window has ``w``
    same-source elements ahead of it, so it can never be among the
    earliest ``need <= w`` — the windows lose nothing.

    The engine calls this with a SMALL ``w`` (a few batch windows):
    topping the front up incrementally keeps N² at a few hundred
    squared — effectively free — where one full-front refill would
    need an N that forces a real sort.  O(num_runs · w²) per refill,
    independent of capacity.
    """
    F, R, S, P = q.front_cap, q.num_runs, q.stage_cap, q.main_phys
    W = F if w is None else min(w, F)
    N = (R + 1) * W

    widx = q.r_off[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    rvalid = widx < q.r_len[:, None]
    wc = jnp.clip(widx, 0, S - 1)
    ct_r = jnp.where(rvalid, jnp.take_along_axis(q.r_times, wc, axis=1),
                     jnp.inf)
    cy_r = jnp.take_along_axis(q.r_types, wc, axis=1)
    ca_r = jnp.take_along_axis(q.r_args, wc[:, :, None], axis=1)
    cs_r = jnp.where(rvalid, jnp.take_along_axis(q.r_seqs, wc, axis=1),
                     _I32_MAX)

    midx = jnp.clip(q.m_head + jnp.arange(W, dtype=jnp.int32), 0, P - 1)
    mvalid = jnp.arange(W) < q.main_n
    ct_m = jnp.where(mvalid, q.m_times[midx], jnp.inf)
    cy_m = q.m_types[midx]
    ca_m = q.m_args[midx]
    cs_m = jnp.where(mvalid, q.m_seqs[midx], _I32_MAX)

    ct = jnp.concatenate([ct_r.reshape(R * W), ct_m])
    cy = jnp.concatenate([cy_r.reshape(R * W), cy_m])
    ca = jnp.concatenate([ca_r.reshape(R * W, -1), ca_m])
    cs = jnp.concatenate([cs_r.reshape(R * W), cs_m])
    src = jnp.concatenate([
        jnp.repeat(jnp.arange(R, dtype=jnp.int32), W),
        jnp.full((W,), R, jnp.int32),
    ])
    valid = jnp.concatenate([rvalid.reshape(R * W), mvalid])

    order = _small_lex_perm(ct, cs)
    ct, cy, ca, cs = ct[order], cy[order], ca[order], cs[order]
    src, valid = src[order], valid[order]

    need = jnp.minimum(F - q.front_n, W)
    # Valid candidates form a sorted prefix (sentinels are lex-max), so
    # the take mask is a prefix too — the taken block lands in front
    # slots [front_n, front_n + taken) already sorted.
    take = (jnp.arange(N) < need) & valid
    taken = jnp.sum(take).astype(jnp.int32)
    counts = jnp.zeros((R + 2,), jnp.int32).at[
        jnp.where(take, src, R + 1)
    ].add(1, mode="drop")

    main_taken = counts[R]
    main_n = q.main_n - main_taken
    i_idx = jnp.arange(F, dtype=jnp.int32)
    srcF = jnp.where(
        i_idx < q.front_n, i_idx,
        F + jnp.clip(i_idx - q.front_n, 0, N - 1),
    )
    fill_ok = i_idx < q.front_n + taken

    def refill(fcol, ccol, fill):
        out = jnp.take(jnp.concatenate([fcol, ccol]), srcF, axis=0)
        mask = fill_ok if out.ndim == 1 else fill_ok[:, None]
        return jnp.where(mask, out, fill)

    return q._replace(
        f_times=refill(q.f_times, ct, jnp.inf),
        f_types=refill(q.f_types, cy, -1),
        f_args=refill(q.f_args, ca, 0.0),
        f_seqs=refill(q.f_seqs, cs, 2**31 - 1),
        front_n=q.front_n + taken,
        r_off=q.r_off + counts[:R],
        main_n=main_n,
        m_head=jnp.where(main_n > 0, q.m_head + main_taken, 0),
    )


def tiered3_queue_peek_front(q: Tiered3DeviceQueue, k: int):
    """Shard-aware entry point: the queue's ``k`` earliest events.

    Refills the front exactly as :func:`tiered3_queue_extract` would
    (the bounded :func:`_refill_front3_windowed` path), then returns
    the first ``k`` front slots WITHOUT popping — free slots read as
    the ``(inf, -1, 0, i32_max)`` sentinels.  The sharded engine merges
    these candidate heads across shards to reconstruct the exact
    global §III-B window, then pops each shard's taken prefix with
    :func:`tiered3_queue_pop_prefix`.

    Returns ``(q', ts, tys, args, seqs)``.
    """
    if k > q.front_cap:
        raise ValueError(
            f"peek width {k} exceeds front tier capacity {q.front_cap}"
        )
    F = q.front_cap
    need_refill = (q.front_n < k) & (
        (q.stage_n > 0) | (q.main_n > 0) | jnp.any(q.r_len > q.r_off)
    )
    # Small k-way top-ups (a few windows' worth) keep the live-run
    # merge in all-pairs territory; the empty-pool path still refills
    # the whole front in one gather.
    q = jax.lax.cond(
        need_refill, _refill_front3_windowed(min(F, 4 * k)),
        lambda q: q, q,
    )
    return q, q.f_times[:k], q.f_types[:k], q.f_args[:k], q.f_seqs[:k]


def tiered3_queue_pop_prefix(q: Tiered3DeviceQueue, length, k: int
                             ) -> Tiered3DeviceQueue:
    """Pop the first ``length`` (<= static ``k``) front events: shift
    every front column left by ``length`` (one fused ``dynamic_slice``
    per column, exactly the :func:`tiered3_queue_extract` pop).  The
    caller must have established ``length <= front_n`` via
    :func:`tiered3_queue_peek_front` — taken candidates are always a
    valid front prefix."""
    F = q.front_cap

    def shift(col, fill):
        pad = jnp.full((k,) + col.shape[1:], fill, col.dtype)
        return jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([col, pad]), length, F
        )

    return q._replace(
        f_times=shift(q.f_times, jnp.inf),
        f_types=shift(q.f_types, -1),
        f_args=shift(q.f_args, 0.0),
        f_seqs=shift(q.f_seqs, 2**31 - 1),
        front_n=q.front_n - length,
        size=q.size - length,
    )


def tiered3_queue_extract(q: Tiered3DeviceQueue, max_len: int, lookaheads,
                          t_cap=None, kernels: str = "xla", bound=None):
    """Window extraction from the front tier (paper Fig 2).

    Identical take rule and output as :func:`tiered_queue_extract`;
    the drained-front refill is the bounded path of
    :func:`_refill_front3_windowed` instead of a staging flush into
    main.  Composed from the shard-aware halves — refill+read
    (:func:`tiered3_queue_peek_front`) and prefix pop
    (:func:`tiered3_queue_pop_prefix`) — so the sharded engine's split
    extraction shares every line with the single-queue path the
    differential suites pin.

    ``kernels="pallas"`` runs the post-refill hot loop (§III-B take
    rule + prefix pop) as one Pallas kernel
    (:func:`repro.kernels.queue_front.window_extract`) — bit-identical
    output, front columns stay in VMEM on TPU, interpret mode
    on CPU.  The bounded refill itself stays in XLA (it is the rare
    amortized path, not the per-batch one).

    ``bound`` optionally caps the candidate set at a lexicographic
    ``(time, seq)`` key: only events strictly lex-BEFORE it are
    eligible.  This is the spill policy's ordering fence — while a
    spilled event is held host-side, nothing at or past its key may
    execute — and since the eligible set is a lex prefix of the sorted
    candidates, the §III-B take rule sees it as the queue simply
    ending earlier (XLA kernels only).

    Returns ``(q', ts, tys, args, length)``.
    """
    if max_len > q.front_cap:
        raise ValueError(
            f"max_len {max_len} exceeds front tier capacity {q.front_cap}"
        )
    k = max_len
    num_types = lookaheads.shape[0]

    if kernels == "pallas":
        if bound is not None:
            raise ValueError(
                "lex-bounded extraction (spill) is XLA-only; use "
                "queue_kernels='xla'"
            )
        from repro.kernels.queue_front import window_extract

        q, _ts_c, _tys_c, _args_c, _seqs_c = tiered3_queue_peek_front(q, k)
        (ts, tys, args, length,
         nf_t, nf_y, nf_a, nf_s) = window_extract(
            q.f_times, q.f_types, q.f_args, q.f_seqs,
            lookaheads, t_cap, k=k,
        )
        q = q._replace(
            f_times=nf_t, f_types=nf_y, f_args=nf_a, f_seqs=nf_s,
            front_n=q.front_n - length,
            size=q.size - length,
        )
        return q, ts, tys, args, length

    q, ts_c, tys_c, args_c, seqs_c = tiered3_queue_peek_front(q, k)
    valid = tys_c >= 0
    if bound is not None:
        b_t, b_s = bound
        valid = valid & (
            (ts_c < b_t) | ((ts_c == b_t) & (seqs_c < b_s))
        )
    la = lookaheads[jnp.clip(tys_c, 0, num_types - 1)]
    wins = jnp.where(valid, ts_c + la, jnp.inf)
    take = window_prefix_mask(ts_c, wins, valid, t_cap)
    length = jnp.sum(take).astype(jnp.int32)

    ts = jnp.where(take, ts_c, 0.0)
    tys = jnp.where(take, tys_c, 0)
    args = jnp.where(take[:, None], args_c, 0.0)

    q = tiered3_queue_pop_prefix(q, length, k)
    return q, ts, tys, args, length


def _tiered3_boundary(q: Tiered3DeviceQueue):
    """Earliest key outside the front tier: min over staging, the run
    summaries, and the main ring head (read at the ring offset — slots
    before ``m_head`` are dead and must not leak into the boundary)."""
    m_min = jnp.where(
        q.main_n > 0,
        jnp.take(q.m_times, jnp.clip(q.m_head, 0, q.main_phys - 1)),
        jnp.inf,
    )
    return jnp.minimum(
        jnp.minimum(m_min, jnp.min(q.s_times)), jnp.min(_run_mins(q))
    )


def _lex_min_pair(t1, s1, t2, s2):
    """Lexicographic min of two ``(time, seq)`` keys (elementwise)."""
    t = jnp.minimum(t1, t2)
    s = jnp.minimum(
        jnp.where(t1 == t, s1, _I32_MAX),
        jnp.where(t2 == t, s2, _I32_MAX),
    )
    return t, s


def _tiered3_boundary_key(q: Tiered3DeviceQueue):
    """Lexicographic ``(time, seq)`` form of :func:`_tiered3_boundary`:
    the earliest full key outside the front tier.  Needed wherever the
    time-only boundary is ambiguous — reabsorbing spilled rows whose
    seqs are older than queued ones (:func:`tiered3_queue_absorb_rows`).
    O(stage_cap + num_runs)."""
    s_t = jnp.min(q.s_times)
    s_s = jnp.min(jnp.where(
        (q.s_times == s_t) & (q.s_types >= 0), q.s_seqs, _I32_MAX
    ))
    r_heads_t = _run_mins(q)
    r_heads_s = jnp.where(
        q.r_len > q.r_off,
        jnp.take_along_axis(
            q.r_seqs, jnp.clip(q.r_off, 0, q.stage_cap - 1)[:, None],
            axis=1,
        )[:, 0],
        _I32_MAX,
    )
    r_t = jnp.min(r_heads_t)
    r_s = jnp.min(jnp.where(r_heads_t == r_t, r_heads_s, _I32_MAX))
    m_idx = jnp.clip(q.m_head, 0, q.main_phys - 1)
    m_t = jnp.where(q.main_n > 0, jnp.take(q.m_times, m_idx), _INF)
    m_s = jnp.where(q.main_n > 0, jnp.take(q.m_seqs, m_idx), _I32_MAX)
    t, s = _lex_min_pair(s_t, s_s, r_t, r_s)
    return _lex_min_pair(t, s, m_t, m_s)


def tiered3_queue_next_key(q: Tiered3DeviceQueue):
    """Full ``(time, seq)`` key of the earliest pending event —
    ``(inf, i32_max)`` when empty.  The lex refinement of
    :func:`tiered3_queue_next_time`, used by the spill policy's
    while-loop guard (no event at or past the spilled bound may run
    before the spill reabsorbs)."""
    b_t, b_s = _tiered3_boundary_key(q)
    t = jnp.where(q.front_n > 0, q.f_times[0], b_t)
    s = jnp.where(q.front_n > 0, q.f_seqs[0], b_s)
    return t, s


def _tiered3_preflush(q: Tiered3DeviceQueue, R: int) -> Tiered3DeviceQueue:
    """Make room for up to ``R`` staging appends (direct + evicted)
    before a fill, via the bounded run-log flush."""
    if R > q.stage_cap:
        raise ValueError(
            f"emit block of {R} rows exceeds stage_cap {q.stage_cap}"
        )
    return jax.lax.cond(
        q.stage_n + R > q.stage_cap, _flush_stage_to_run, lambda q: q, q
    )


def tiered3_queue_fill_rows(q: Tiered3DeviceQueue, rows,
                            kernels: str = "xla") -> Tiered3DeviceQueue:
    """Per-batch emit insert touching only the front and staging tiers.

    Same partition and accounting as :func:`tiered_queue_fill_rows`
    (the shared :func:`_tiered_fill_finish`; boundary now spans staging
    ∪ runs ∪ main; drop rule unchanged: valid row ``r`` is a ghost iff
    ``size + r >= capacity``), but the pre-flush when staging could
    overflow writes one sorted run (O(stage_cap),
    capacity-independent) instead of merging into main — near-full
    near-head pressure no longer touches an O(capacity) path on any
    per-batch route.  No eviction tags: runs keep true seqs and every
    downstream merge is a true ``(time, seq)`` lex sort.
    """
    rows = jnp.asarray(rows, jnp.float32)
    q = _tiered3_preflush(q, rows.shape[0])
    seq_r, insert, counters = _default_fill_accounting(q, rows)
    return _tiered_fill_finish(
        q, rows, _tiered3_boundary(q), seq_r, insert, counters,
        kernels=kernels,
    )


def tiered3_queue_fill_rows_tagged(q: Tiered3DeviceQueue, rows, seqs,
                                   insert, kernels: str = "xla"
                                   ) -> Tiered3DeviceQueue:
    """Shard-aware emit insert: seqs and survival are decided UPSTREAM.

    The sharded engine assigns seqs from ONE global counter across all
    shards and applies the reference overflow rule against the GLOBAL
    logical capacity, then routes each row to its destination shard —
    so this entry point takes ``seqs`` (i32[R], must exceed every seq
    already queued in any shard) and ``insert`` (bool[R], the rows this
    shard actually absorbs: globally surviving AND routed here) instead
    of deriving them from the local counters.  Rows outside ``insert``
    are ignored entirely (ghost accounting lives in the engine's global
    counters), so the local ``size`` tracks real occupancy and
    ``dropped`` stays 0 on shard queues.  Merge mechanics are byte-for-
    byte the single-queue path (:func:`_tiered_fill_finish`).
    """
    rows = jnp.asarray(rows, jnp.float32)
    seqs = jnp.asarray(seqs, jnp.int32)
    q = _tiered3_preflush(q, rows.shape[0])
    insert = insert & (rows[:, 1] >= 0)
    n_ins = jnp.sum(insert).astype(jnp.int32)
    counters = dict(
        size=q.size + n_ins,
        next_seq=jnp.maximum(
            q.next_seq, jnp.max(jnp.where(insert, seqs + 1, 0))
        ),
        dropped=q.dropped,
    )
    return _tiered_fill_finish(
        q, rows, _tiered3_boundary(q), seqs, insert, counters,
        kernels=kernels,
    )


@jax.named_scope(ABSORB)
def tiered3_queue_absorb_rows(q: Tiered3DeviceQueue, rows, seqs,
                              insert=None) -> Tiered3DeviceQueue:
    """Absorb out-of-band rows carrying externally assigned seqs.

    Two callers: the overflow='spill' policy reabsorbing previously
    spilled rows at a segment boundary, and the streaming ingest path
    absorbing arrival blocks (DESIGN.md §10).  Unlike fresh emits, the
    rows' seqs may be OLDER than seqs queued after them, so both the
    boundary partition and the front-merge placement must compare full
    lexicographic ``(time, seq)`` keys — the ``b_seq`` mode of
    :func:`_tiered_fill_finish`.  Counters follow the occupancy
    discipline of the tagged fill (``size`` = real occupancy,
    ``dropped`` untouched, ``next_seq`` maxed past every absorbed seq);
    the caller guarantees the inserted rows fit (occupancy + inserted
    <= capacity) — absorption never drops.

    ``insert`` optionally masks rows (ANDed with ``type >= 0``): the
    streamed admission path uses a traced ``[lo, hi)`` prefix mask so
    one jitted absorb serves any admitted-row count.

    Host-driven (segment boundaries, off the hot path): rows are
    chunked to ``stage_cap`` so each chunk satisfies the preflush
    contract.  Row layout ``(time, type, arg...)``; ``type < 0`` rows
    are skipped.
    """
    rows = jnp.asarray(rows, jnp.float32)
    seqs = jnp.asarray(seqs, jnp.int32)
    S = q.stage_cap
    for start in range(0, int(rows.shape[0]), S):
        chunk = rows[start:start + S]
        chunk_seqs = seqs[start:start + S]
        q = _tiered3_preflush(q, int(chunk.shape[0]))
        insert_c = chunk[:, 1] >= 0
        if insert is not None:
            insert_c = insert_c & jnp.asarray(insert)[start:start + S]
        n_ins = jnp.sum(insert_c).astype(jnp.int32)
        counters = dict(
            size=q.size + n_ins,
            next_seq=jnp.maximum(
                q.next_seq,
                jnp.max(jnp.where(insert_c, chunk_seqs + 1, 0)),
            ),
            dropped=q.dropped,
        )
        b_t, b_s = _tiered3_boundary_key(q)
        q = _tiered_fill_finish(
            q, chunk, b_t, chunk_seqs, insert_c, counters, b_seq=b_s
        )
    return q


def tiered3_queue_to_flat(q: Tiered3DeviceQueue) -> DeviceQueue:
    """Canonical flat view of a tiered3 queue (host-side, for tests)."""
    head, main_n = int(q.m_head), int(q.main_n)
    off = np.asarray(q.r_off)
    rlen = np.asarray(q.r_len)
    parts = []
    for pre in ("f", "s"):
        parts.append(tuple(
            np.asarray(getattr(q, f"{pre}_{name}"))
            for name in ("times", "types", "args", "seqs")
        ))
    mcols = tuple(
        np.asarray(getattr(q, f"m_{name}"))[head:head + main_n]
        for name in ("times", "types", "args", "seqs")
    )
    parts.append(mcols)
    for i in range(q.num_runs):
        parts.append(tuple(
            np.asarray(getattr(q, f"r_{name}"))[i, off[i]:rlen[i]]
            for name in ("times", "types", "args", "seqs")
        ))
    times = np.concatenate([p[0] for p in parts])
    types = np.concatenate([p[1] for p in parts])
    args = np.concatenate([p[2] for p in parts])
    seqs = np.concatenate([p[3] for p in parts])
    occ = types >= 0
    order = np.lexsort((seqs[occ], times[occ]))
    n = int(occ.sum())
    C = q.capacity
    assert n <= C, "tier occupancy exceeded logical capacity"
    out_t = np.full((C,), np.inf, np.float32)
    out_y = np.full((C,), -1, np.int32)
    out_a = np.zeros((C, q.f_args.shape[1]), np.float32)
    out_s = np.full((C,), 2**31 - 1, np.int32)
    out_t[:n] = times[occ][order]
    out_y[:n] = types[occ][order]
    out_a[:n] = args[occ][order]
    out_s[:n] = seqs[occ][order]
    return DeviceQueue(
        times=jnp.asarray(out_t), types=jnp.asarray(out_y),
        args=jnp.asarray(out_a), seqs=jnp.asarray(out_s),
        size=jnp.asarray(q.size), next_seq=jnp.asarray(q.next_seq),
        dropped=jnp.asarray(q.dropped),
    )


# ---------------------------------------------------------------------------
# Stacked-axis variants (devices placement, DESIGN.md §12)
# ---------------------------------------------------------------------------
# A "stacked" queue is a Tiered3DeviceQueue pytree whose every leaf
# carries a leading shard axis of size N — the layout the sharded
# engine's ``placement="devices"`` path shards across a 1-D mesh (one
# shard per device).  These entry points lift the per-shard ops over
# that axis with ``vmap``.  Inside the engine's ``shard_map``ped loop
# the PLAIN per-shard ops run on the squeezed local queue (preserving
# the rare-path ``lax.cond``s that vmap would lower to
# both-branches-execute selects); the vmapped forms serve the host-side
# and segment-boundary paths — occupancy/next-time summaries, stream
# absorption — where a select'd rare path is irrelevant, and pin the
# stacked layout's semantics testably against the unrolled tuple loop.

def tiered3_stacked_has_pending(q):
    """Per-shard pending flags, ``bool[N]``."""
    return jax.vmap(tiered3_queue_has_pending)(q)


def tiered3_stacked_occupancy(q):
    """Per-shard real occupancy, ``i32[N]``."""
    return jax.vmap(tiered3_queue_occupancy)(q)


def tiered3_stacked_next_time(q):
    """Per-shard earliest pending timestamp, ``f32[N]``."""
    return jax.vmap(tiered3_queue_next_time)(q)


def tiered3_stacked_next_key(q):
    """Per-shard earliest ``(time, seq)`` lex keys, ``(f32[N], i32[N])``."""
    return jax.vmap(tiered3_queue_next_key)(q)


def tiered3_stacked_peek_front(q, k: int):
    """:func:`tiered3_queue_peek_front` over the shard axis: returns
    ``(q', ts[N,k], tys[N,k], args[N,k,W], seqs[N,k])``."""
    return jax.vmap(lambda s: tiered3_queue_peek_front(s, k))(q)


def tiered3_stacked_pop_prefix(q, lengths, k: int):
    """:func:`tiered3_queue_pop_prefix` over the shard axis —
    ``lengths`` is ``i32[N]``, each shard pops its own prefix."""
    lengths = jnp.asarray(lengths, jnp.int32)
    return jax.vmap(lambda s, n: tiered3_queue_pop_prefix(s, n, k))(
        q, lengths)


def tiered3_stacked_fill_rows_tagged(q, rows, seqs, insert,
                                     kernels: str = "xla"):
    """:func:`tiered3_queue_fill_rows_tagged` over the shard axis: the
    R-row exchange block ``rows``/``seqs`` is shared (replicated), the
    ``insert`` mask is per shard (``bool[N, R]`` — row r lands in shard
    i iff ``insert[i, r]``)."""
    return jax.vmap(
        lambda s, m: tiered3_queue_fill_rows_tagged(
            s, rows, seqs, m, kernels=kernels)
    )(q, jnp.asarray(insert))


def tiered3_stacked_absorb_rows(q, rows, seqs, insert):
    """:func:`tiered3_queue_absorb_rows` over the shard axis (streamed
    arrival absorption at segment boundaries): shared rows/seqs, a
    per-shard ``insert`` mask of shape ``(N, rows)``."""
    return jax.vmap(
        lambda s, m: tiered3_queue_absorb_rows(s, rows, seqs, insert=m)
    )(q, jnp.asarray(insert))
