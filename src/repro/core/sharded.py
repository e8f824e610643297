"""Sharded device engine: lookahead-synchronized multi-queue execution.

PARSIR-style conservative PDES (PAPERS.md) scales past one processor by
partitioning the pending set across engines and letting each run ahead
only as far as a lookahead-bounded horizon.  :class:`ShardedDeviceEngine`
brings that structure to the on-device runtime: entities are partitioned
across ``shards`` per-shard :class:`~repro.core.queue.Tiered3DeviceQueue`
pending sets, each super-step synchronizes the shard clocks under a
shared conservative horizon, and cross-shard emissions travel through
fixed-capacity exchange blocks merged into the destination queues with
the same bounded counting-merge primitives the single queue uses (no
sorts, no scatters — the XLA:CPU traps, DESIGN.md §4.4).

The horizon, honestly
---------------------
Every super-step:

1. **peek** — each shard surfaces its ``max_batch_len`` earliest events
   (:func:`~repro.core.queue.tiered3_queue_peek_front`: the tiered3
   front tier after its bounded refill), O(front_cap) per shard.
2. **merge** — the ``shards × max_batch_len`` candidate heads are
   lex-ordered by their true global ``(time, seq)`` keys (all-pairs
   rank — the candidate set is tiny) and the §III-B dynamic-lookahead
   take rule (:func:`~repro.core.queue.window_prefix_mask`) runs over
   the first ``max_batch_len`` of the merged order.  Because every
   pending event is among its own shard's ``max_batch_len`` earliest
   whenever it is among the ``max_batch_len`` globally earliest, this
   reconstructs EXACTLY the window the single-queue engine would
   extract.  The window's dynamic bound ``min over taken (t_j + l_j)``
   is the conservative synchronization horizon; it is bounded below by
   ``min_i(next_time_i) + min_lookahead`` — the classic conservative
   floor (no shard can receive a cross-shard event below it) — but the
   merged evaluation is exact where the floor alone would under- or
   over-take.
3. **pop** — the take set is a prefix of the merged order, so each
   shard's taken events are a prefix of its own candidates; shard ``i``
   pops its count with one
   :func:`~repro.core.queue.tiered3_queue_pop_prefix` shift.
4. **dispatch** — the merged window runs through the identical
   composed-batch dispatch path as :class:`~repro.core.engine
   .DeviceEngine` (switch or vmapped entity runs), so the state update
   is bit-identical.
5. **exchange** — emitted rows get seqs from ONE global counter
   (``next_seq + vrank``, the reference rule) and the global overflow
   rule (ghost iff ``size + vrank >= capacity``, ``size`` counting
   ghosts) — both computed BEFORE routing, so accounting cannot depend
   on the partition.  Each destination shard then absorbs its routed
   rows from the fixed ``max_batch_len × max_emit``-row exchange block
   via :func:`~repro.core.queue.tiered3_queue_fill_rows_tagged` — the
   single-queue counting-merge fill with seqs/survival supplied.

Because each super-step reproduces the single-queue window exactly —
same events, same order, same batch grouping, same seqs, same ghosts —
the sharded run is bit-identical to ``queue_mode="tiered3"`` with one
queue: final state, executed (time, seq) sequence, ``dropped``,
``final_time``, and even ``batches``.  The executable contract lives in
``tests/test_sharded_engine.py`` and the shared parity harness
(``tests/_parity.py``).

Compilation shape (``placement="serial"``)
------------------------------------------
The shard queues are a TUPLE of :class:`Tiered3DeviceQueue` pytrees and
the per-shard legs (peek, pop, exchange fill) are an unrolled Python
loop, so each shard's buffers thread through the ``while_loop`` carry
as separate arrays that XLA updates IN PLACE — per-super-step cost
stays bounded (capacity-independent) like the single queue's.  Two
tempting alternatives are wrong at scale and were measured so:
``lax.scan`` over stacked shards compiles the machinery once (~4×
faster compile at N=4) but its xs/ys slicing re-materializes every
shard's capacity-sized leaves every super-step — O(N·capacity) memcpy
per batch, ~45–100× slower at 64k and GROWING with capacity; ``vmap``
additionally lowers the rare-path ``lax.cond``s to select pairs that
execute both branches (including the O(capacity) ring rotate) for
every shard every step.  Compile time is therefore linear in
``shards`` (~7 s per shard on CPU) — the price of bounded runtime.

Device placement (``placement="devices"``, DESIGN.md §12)
---------------------------------------------------------
``placement="devices"`` removes both the serialized per-shard legs and
the N-linear compile cost: the N per-shard queues are STACKED along a
leading shard axis (:class:`StackedShardedQueue`), sharded across a
1-D ``"shards"`` mesh (:func:`repro.launch.mesh.make_shard_mesh`), and
the whole while-loop runs inside ONE ``shard_map`` — each device owns
one shard's queue, runs the identical peek/pop/fill leg on its local
(squeezed) :class:`Tiered3DeviceQueue`, and compiles the machinery
once (SPMD).  Per super-step, exactly one thing crosses device
boundaries: the ``all_gather`` of the N k-row head slabs for the
global merge.  The merge, window rule, dispatch, and global
seq/ghost accounting are computed REPLICATED on every device
(deterministic redundant compute), so the R-row exchange needs no
collective at all — every device holds the full replicated emit block
and masks its own inserts with ``dest == axis_index``, the same
masked :func:`~repro.core.queue.tiered3_queue_fill_rows_tagged` the
serial path uses per shard.  The global counters, the horizon state,
and the admission fence stay replicated scalars; the while-loop guard
reads them from the carry (collectives cannot appear in a ``cond``),
refreshed in the body from one more small gather of per-shard
head keys.  ``placement="serial"`` remains the executable spec: the
two paths are asserted bit-identical on the full parity matrix, and a
host without enough devices can force them
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``).

Routing
-------
``shard_fn(tys, args) -> i32[rows]`` maps each emitted event to a
shard.  The default routes by ``arg[0]`` — the entity index of
entity-parallel types (``@prog.entity_handler`` puts the entity id
there) and the conventional routing slot of emitting types (PHOLD's
destination LP, the serving scenario's request id) — reduced mod
``shards``.  Any deterministic routing is CORRECT (parity never depends
on the partition, only load balance does); results of a custom
``shard_fn`` are reduced mod ``shards`` so no row can be lost to an
out-of-range destination.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import validate as _validate
from repro.core.engine import DeviceEngine
from repro.core.events import ARG_WIDTH
from repro.core.validate import FAULT_CLOCK
from repro.core.queue import (
    DeviceQueue,
    Tiered3DeviceQueue,
    _prefix_rank,
    _small_lex_perm,
    tiered3_queue_absorb_rows,
    tiered3_queue_fill_rows_tagged,
    tiered3_queue_from_host,
    tiered3_queue_has_pending,
    tiered3_queue_next_key,
    tiered3_queue_next_time,
    tiered3_queue_occupancy,
    tiered3_queue_peek_front,
    tiered3_queue_pop_prefix,
    tiered3_queue_to_flat,
    tiered3_stacked_absorb_rows,
    tiered3_stacked_next_time,
    tiered3_stacked_occupancy,
    window_prefix_mask,
)

__all__ = [
    "ShardedDeviceEngine",
    "ShardedQueue",
    "StackedShardedQueue",
    "place_stacked_queue",
    "sharded_queue_to_flat",
    "stack_sharded_queue",
]


class ShardedQueue(NamedTuple):
    """The sharded pending set (a JAX pytree): N per-shard tiered3
    queues plus the GLOBAL logical counters.

    The global counters carry the reference overflow/seq semantics —
    ``size`` counts logical pushes including ghosts, ``next_seq`` is
    the one seq counter all shards share, ``dropped`` the global ghost
    count — while each shard's local ``size`` tracks only its real
    occupancy (shard-local ``dropped`` stays 0; see
    :func:`~repro.core.queue.tiered3_queue_fill_rows_tagged`).  The
    logical capacity is the single-queue ``capacity`` (each shard can
    physically hold all of it, so routing skew never causes drops the
    single queue would not have had).
    """

    shards: tuple[Tiered3DeviceQueue, ...]
    size: jnp.ndarray      # i32 scalar, global logical pushes (+ghosts)
    next_seq: jnp.ndarray  # i32 scalar, global seq counter
    dropped: jnp.ndarray   # i32 scalar, global overflow drops

    @property
    def num_shards(self) -> int:
        return len(self.shards)

    @property
    def capacity(self) -> int:
        return self.shards[0].capacity

    def shard(self, i: int) -> Tiered3DeviceQueue:
        return self.shards[i]


def sharded_queue_to_flat(sq: ShardedQueue) -> DeviceQueue:
    """Canonical flat view of a sharded queue (host-side, for tests).

    Gathers every shard's occupied slots, sorts by the global
    ``(time, seq)`` key, and lays them out as one canonical
    :class:`~repro.core.queue.DeviceQueue` carrying the GLOBAL
    counters — directly comparable to the single-queue flat views.
    """
    cols = []
    for i in range(sq.num_shards):
        flat = tiered3_queue_to_flat(sq.shard(i))
        occ = np.asarray(flat.types) >= 0
        cols.append((np.asarray(flat.times)[occ], np.asarray(flat.types)[occ],
                     np.asarray(flat.args)[occ], np.asarray(flat.seqs)[occ]))
    times = np.concatenate([c[0] for c in cols])
    types = np.concatenate([c[1] for c in cols])
    args = np.concatenate([c[2] for c in cols])
    seqs = np.concatenate([c[3] for c in cols])
    order = np.lexsort((seqs, times))
    n = times.shape[0]
    C = sq.capacity
    assert n <= C, "sharded occupancy exceeded global logical capacity"
    out_t = np.full((C,), np.inf, np.float32)
    out_y = np.full((C,), -1, np.int32)
    out_a = np.zeros((C, args.shape[1]), np.float32)
    out_s = np.full((C,), 2**31 - 1, np.int32)
    out_t[:n], out_y[:n], out_a[:n], out_s[:n] = (
        times[order], types[order], args[order], seqs[order]
    )
    return DeviceQueue(
        times=jnp.asarray(out_t), types=jnp.asarray(out_y),
        args=jnp.asarray(out_a), seqs=jnp.asarray(out_s),
        size=jnp.asarray(sq.size), next_seq=jnp.asarray(sq.next_seq),
        dropped=jnp.asarray(sq.dropped),
    )


class StackedShardedQueue(NamedTuple):
    """The sharded pending set in device placement (DESIGN.md §12): the
    N per-shard tiered3 queues stacked along a leading shard axis, so
    every leaf of ``q`` has shape ``(N, ...)`` and can be sharded over
    the 1-D ``"shards"`` mesh — each device holds exactly its shard's
    slice.  The global counters keep the :class:`ShardedQueue`
    semantics and stay REPLICATED scalars.

    ``shards``/``shard(i)`` unstack host-side views so every consumer
    written against :class:`ShardedQueue` — ``sharded_queue_to_flat``,
    the full audit, the segment-boundary next-time probe — works on
    either layout unchanged.
    """

    q: Tiered3DeviceQueue  # leaves stacked: leading axis = shard
    size: jnp.ndarray      # i32 scalar, global logical pushes (+ghosts)
    next_seq: jnp.ndarray  # i32 scalar, global seq counter
    dropped: jnp.ndarray   # i32 scalar, global overflow drops

    @property
    def num_shards(self) -> int:
        return int(self.q.f_times.shape[0])

    @property
    def capacity(self) -> int:
        # Shape-derived, per the unstacked queue's property (leading
        # axis is the shard axis here).
        return (self.q.m_times.shape[1]
                - self.q.r_times.shape[1] * self.q.s_times.shape[1])

    @property
    def shards(self) -> tuple[Tiered3DeviceQueue, ...]:
        return tuple(self.shard(i) for i in range(self.num_shards))

    def shard(self, i: int) -> Tiered3DeviceQueue:
        return jax.tree_util.tree_map(lambda x: x[i], self.q)


def stack_sharded_queue(sq: ShardedQueue) -> StackedShardedQueue:
    """Stack a tuple-of-shards queue along a new leading shard axis."""
    q = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *sq.shards)
    return StackedShardedQueue(q=q, size=sq.size, next_seq=sq.next_seq,
                               dropped=sq.dropped)


def place_stacked_queue(stq: StackedShardedQueue,
                        mesh) -> StackedShardedQueue:
    """Place a stacked queue on the ``"shards"`` mesh: one shard slice
    per device, global counters replicated.  Also the re-placement hook
    after a checkpoint restore (restored leaves land on the default
    device)."""
    row = NamedSharding(mesh, P("shards"))
    rep = NamedSharding(mesh, P())
    q = jax.tree_util.tree_map(lambda x: jax.device_put(x, row), stq.q)
    return StackedShardedQueue(
        q=q,
        size=jax.device_put(jnp.asarray(stq.size), rep),
        next_seq=jax.device_put(jnp.asarray(stq.next_seq), rep),
        dropped=jax.device_put(jnp.asarray(stq.dropped), rep),
    )


@dataclasses.dataclass
class ShardedDeviceEngine(DeviceEngine):
    """Multi-queue device engine, bit-identical to the single queue.

    Preferred entry point: ``repro.api.SimProgram.build(
    backend="device", shards=N)``.  Direct usage mirrors
    :class:`~repro.core.engine.DeviceEngine`::

        eng = ShardedDeviceEngine(registry, shards=4, capacity=65536,
                                  max_batch_len=8)
        queue = eng.initial_queue(events)     # -> ShardedQueue
        state, queue, stats = eng.run(state0, queue)

    All :class:`DeviceEngine` knobs apply per shard (each shard is a
    full tiered3 queue with the same ``front_cap``/``stage_cap``/
    ``num_runs`` geometry); ``queue_mode`` must remain ``"tiered3"``
    (the per-shard pending-set implementation this engine is built
    on).  ``shard_fn`` customizes event routing (module docstring) —
    it must be a pure jnp function of ``(tys, args)``; its result is
    reduced mod ``shards``.  The queue argument to :meth:`run` is
    donated exactly as in the parent.
    """

    shards: int = 2
    shard_fn: Callable | None = None
    placement: str = "serial"

    def __post_init__(self, use_vectorized_queue):
        if self.queue_mode != "tiered3":
            raise ValueError(
                f"ShardedDeviceEngine requires queue_mode='tiered3' "
                f"(got {self.queue_mode!r}): the per-shard pending sets "
                "are tiered3 queues"
            )
        if self.shards < 1:
            raise ValueError(f"shards must be >= 1, got {self.shards}")
        if self.overflow == "spill":
            raise ValueError(
                "overflow='spill' is not supported on the sharded engine "
                "yet: the spill fence is a single-queue lex bound "
                "(use overflow='drop' or 'error')"
            )
        if self.placement not in ("serial", "devices"):
            raise ValueError(
                f"placement must be 'serial' or 'devices', "
                f"got {self.placement!r}"
            )
        if self.placement == "devices":
            from repro.launch.mesh import make_shard_mesh
            self._mesh = make_shard_mesh(self.shards)
        else:
            self._mesh = None
        super().__post_init__(use_vectorized_queue)

    @classmethod
    def from_program(cls, program, *, shards: int = 2,
                     shard_fn: Callable | None = None,
                     placement: str = "serial",
                     queue_mode: str = "tiered3",
                     capacity: int | None = None,
                     front_cap: int | None = None,
                     stage_cap: int | None = None,
                     num_runs: int | None = None,
                     dispatch_mode: str | None = None,
                     hot_words=None,
                     queue_kernels: str = "xla",
                     validate: str = "off",
                     overflow: str = "drop",
                     t_end: float = float("inf")) -> "ShardedDeviceEngine":
        """Construct the sharded device backend from a frozen SimProgram
        (cf. :meth:`DeviceEngine.from_program`; the entity→shard mapping
        falls out of the entity-handler ``arg[0]`` convention unless a
        ``shard_fn`` overrides it)."""
        cfg = program.config
        return cls(
            program.device_registry(),
            max_batch_len=cfg.max_batch_len,
            capacity=cfg.capacity if capacity is None else capacity,
            max_emit=cfg.max_emit,
            t_end=t_end,
            queue_mode=queue_mode,
            front_cap=front_cap,
            stage_cap=stage_cap,
            num_runs=num_runs,
            dispatch_mode=dispatch_mode,
            hot_words=hot_words,
            queue_kernels=queue_kernels,
            validate=validate,
            overflow=overflow,
            entity_handlers=program.device_entity_handlers() or None,
            shards=shards,
            shard_fn=shard_fn,
            placement=placement,
        )

    # -- routing ------------------------------------------------------------
    def _shard_of(self, tys, args):
        """Destination shard per row, always in ``[0, shards)``."""
        if self.shard_fn is not None:
            dest = jnp.asarray(self.shard_fn(tys, args), jnp.int32)
        else:
            dest = jnp.abs(args[:, 0].astype(jnp.int32))
        return dest % jnp.int32(self.shards)

    # -- queue construction -------------------------------------------------
    def initial_queue(self, events) -> ShardedQueue:
        """Partition the seed across shards under the GLOBAL seq and
        overflow rules: event ``i`` keeps seq ``i`` and is a ghost iff
        ``i >= capacity`` (the reference ``from_host`` semantics),
        THEN the survivors are routed — so the seed is bit-equivalent
        to the single queue's regardless of the partition."""
        events = list(events)
        n = len(events)
        C = self.capacity
        survivors = events[:C]
        if survivors:
            tys = jnp.asarray([ty for (_, ty, _) in survivors], jnp.int32)
            args = np.zeros((len(survivors), ARG_WIDTH), np.float32)
            for i, (_, _, arg) in enumerate(survivors):
                if arg is not None:
                    args[i] = np.asarray(arg, np.float32)
            dest = np.asarray(self._shard_of(tys, jnp.asarray(args)))
        else:
            dest = np.zeros((0,), np.int32)
        shard_qs = []
        for s in range(self.shards):
            mine = np.flatnonzero(dest == s)
            shard_qs.append(tiered3_queue_from_host(
                [survivors[i] for i in mine], C,
                front_cap=self.front_cap, stage_cap=self.stage_cap,
                num_runs=self.num_runs, seqs=mine,
            ))
        sq = ShardedQueue(
            shards=tuple(shard_qs),
            size=jnp.int32(n),
            next_seq=jnp.int32(n),
            dropped=jnp.int32(n - len(survivors)),
        )
        if self.placement == "devices":
            return place_stacked_queue(stack_sharded_queue(sq), self._mesh)
        return sq

    def place_queue(self, queue):
        """Re-place a queue on this engine's mesh (after a checkpoint
        restore lands every leaf on the default device)."""
        if isinstance(queue, StackedShardedQueue):
            return place_stacked_queue(queue, self._mesh)
        return queue

    # -- run accounting -----------------------------------------------------
    def queue_occupancy(self, queue):
        """Real pending-event count summed across shards."""
        if isinstance(queue, StackedShardedQueue):
            return jnp.sum(tiered3_stacked_occupancy(queue.q)).astype(
                jnp.int32)
        return sum(
            (tiered3_queue_occupancy(q) for q in queue.shards),
            jnp.int32(0),
        )

    def queue_next_time(self, queue):
        """Earliest pending timestamp across shards (``inf`` when
        empty)."""
        if isinstance(queue, StackedShardedQueue):
            return jnp.min(tiered3_stacked_next_time(queue.q))
        return jnp.min(jnp.stack(
            [tiered3_queue_next_time(q) for q in queue.shards]))

    def _cheap_fault_bits(self, queue):
        if isinstance(queue, StackedShardedQueue):
            return _validate.stacked_sharded_fault_bits(queue)
        return _validate.sharded_fault_bits(queue)

    def absorb_rows(self, sq, rows, seqs, insert):
        """Absorb stream-arrival rows where ``insert`` is set: route
        through ``shard_fn`` like any exchange, absorb per shard under
        the full lex key, and advance the GLOBAL counters (``size`` by
        the inserted count — the occupancy discipline; ``dropped``
        untouched).  Caller guarantees the masked rows fit globally."""
        rows = jnp.asarray(rows, jnp.float32)
        seqs = jnp.asarray(seqs, jnp.int32)
        insert = jnp.asarray(insert) & (rows[:, 1] >= 0)
        dest = self._shard_of(rows[:, 1].astype(jnp.int32), rows[:, 2:])
        n_ins = jnp.sum(insert).astype(jnp.int32)
        next_seq = jnp.maximum(
            sq.next_seq, jnp.max(jnp.where(insert, seqs + 1, 0))
        )
        if isinstance(sq, StackedShardedQueue):
            # Segment-boundary path (once per arrival block): the
            # vmapped absorb is fine here — its cond→select lowering
            # never runs inside the hot while-loop.
            ins_nk = insert[None, :] & (
                dest[None, :]
                == jnp.arange(self.shards, dtype=jnp.int32)[:, None]
            )
            return StackedShardedQueue(
                q=tiered3_stacked_absorb_rows(sq.q, rows, seqs, ins_nk),
                size=sq.size + n_ins,
                next_seq=next_seq,
                dropped=sq.dropped,
            )
        shard_qs = tuple(
            tiered3_queue_absorb_rows(q, rows, seqs,
                                      insert=insert & (dest == i))
            for i, q in enumerate(sq.shards)
        )
        return ShardedQueue(
            shards=shard_qs,
            size=sq.size + n_ins,
            next_seq=next_seq,
            dropped=sq.dropped,
        )

    # -- main loop ----------------------------------------------------------
    def _run(self, state, queue, t_end, max_batches, stats0):
        if isinstance(queue, StackedShardedQueue):
            return self._run_devices(state, queue, t_end, max_batches,
                                     stats0)
        k = self.max_batch_len
        N = self.shards
        num_types = len(self.registry)
        lookaheads = self._lookaheads
        validate_on = self.validate != "off"
        # Streamed-arrival admission fence (DESIGN.md §10): carried
        # structurally, exactly as in the single-queue engine — closed
        # runs compile a fence-free loop.
        fenced = "bound_t" in stats0
        I32_MAX = jnp.int32(2**31 - 1)

        def cond(carry):
            state, sq, stats = carry
            del state
            pending = jnp.any(jnp.stack(
                [tiered3_queue_has_pending(q) for q in sq.shards]
            ))
            next_t = jnp.min(jnp.stack(
                [tiered3_queue_next_time(q) for q in sq.shards]
            ))
            ok = (
                pending
                & (stats["batches"] < max_batches)
                & (next_t <= t_end)
            )
            if validate_on:
                ok = ok & (stats["fault_word"] == 0)
            if self.overflow == "error":
                ok = ok & (sq.dropped == 0)
            if fenced:
                # The globally earliest pending (time, seq) must be
                # lex-below the bound, else the segment ends and the
                # host absorbs the next arrival block first.
                keys = [tiered3_queue_next_key(q) for q in sq.shards]
                kt = jnp.stack([t for t, _ in keys])
                ks = jnp.stack([s for _, s in keys])
                nk_t = jnp.min(kt)
                nk_s = jnp.min(jnp.where(kt == nk_t, ks, I32_MAX))
                below = (nk_t < stats["bound_t"]) | (
                    (nk_t == stats["bound_t"])
                    & (nk_s < stats["bound_seq"])
                )
                ok = ok & below
            return ok

        def body(carry):
            state, sq, stats = carry

            # 1. peek: each shard's earliest k events (bounded refill).
            # Unrolled per shard — NOT a scan/vmap — so each shard's
            # capacity-sized buffers thread the while-loop carry as
            # separate in-place arrays (module docstring: scan's xs/ys
            # slicing would copy O(N·capacity) per super-step).
            peeked = [tiered3_queue_peek_front(q, k) for q in sq.shards]
            qs = [p[0] for p in peeked]
            cts = jnp.concatenate([p[1] for p in peeked])
            ctys = jnp.concatenate([p[2] for p in peeked])
            cargs = jnp.concatenate([p[3] for p in peeked])
            cseqs = jnp.concatenate([p[4] for p in peeked])
            csrc = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)

            # 2. merge + exact global window (the horizon evaluation).
            order = _small_lex_perm(cts, cseqs)[:k]
            ts_c = cts[order]
            tys_c = ctys[order]
            args_c = cargs[order]
            src_c = csrc[order]
            valid = tys_c >= 0
            if fenced:
                # Candidates at/past the admission bound are invisible
                # this super-step; they form a suffix of the lex-merged
                # order, so the §III-B prefix take rule is unaffected.
                seqs_c = cseqs[order]
                valid = valid & (
                    (ts_c < stats["bound_t"])
                    | ((ts_c == stats["bound_t"])
                       & (seqs_c < stats["bound_seq"]))
                )
            la = lookaheads[jnp.clip(tys_c, 0, num_types - 1)]
            wins = jnp.where(valid, ts_c + la, jnp.inf)
            take = window_prefix_mask(ts_c, wins, valid, t_end)
            length = jnp.sum(take).astype(jnp.int32)

            ts = jnp.where(take, ts_c, 0.0)
            tys = jnp.where(take, tys_c, 0)
            args = jnp.where(take[:, None], args_c, 0.0)

            # 3. pop each shard's taken prefix.
            qs = [
                tiered3_queue_pop_prefix(
                    qs[i],
                    jnp.sum(take & (src_c == i)).astype(jnp.int32),
                    k,
                )
                for i in range(N)
            ]

            # 4. dispatch: the parent's composed-batch path, verbatim.
            state, emits = self._dispatch_window(state, ts, tys, args,
                                                 length)

            # 5. global seq + overflow accounting (reference rule; the
            # insert-time size is POST-extract, as in the single queue).
            ty_r = emits[:, 1].astype(jnp.int32)
            valid_r = ty_r >= 0
            vrank = _prefix_rank(valid_r)
            num_valid = jnp.sum(valid_r).astype(jnp.int32)
            size_mid = sq.size - length
            insert = valid_r & (size_mid + vrank < self.capacity)
            num_insert = jnp.sum(insert).astype(jnp.int32)
            seq_r = sq.next_seq + vrank

            # 6. exchange: route rows; each shard absorbs its slice of
            # the fixed R-row exchange block.
            dest = self._shard_of(ty_r, emits[:, 2:])
            qs = [
                tiered3_queue_fill_rows_tagged(
                    qs[i], emits, seq_r, insert & (dest == i),
                    kernels=self.queue_kernels,
                )
                for i in range(N)
            ]

            sq = ShardedQueue(
                shards=tuple(qs),
                size=size_mid + num_valid,
                next_seq=sq.next_seq + num_valid,
                dropped=sq.dropped + (num_valid - num_insert),
            )
            last_t = ts[jnp.maximum(length - 1, 0)]
            prev_time = stats["time"]
            new_stats = {
                "batches": stats["batches"] + 1,
                "events": stats["events"] + length,
                "emitted": stats["emitted"] + num_valid,
                "time": jnp.maximum(stats["time"], last_t),
            }
            self._count_window(stats, new_stats, tys, length)
            if fenced:
                new_stats["bound_t"] = stats["bound_t"]
                new_stats["bound_seq"] = stats["bound_seq"]
            if validate_on:
                bits = self._cheap_fault_bits(sq)
                bits = bits | jnp.where(
                    (length > 0) & (ts[0] < prev_time),
                    jnp.int32(FAULT_CLOCK), jnp.int32(0),
                )
                # Word only — the faulting step is reconstructed from
                # ``batches`` at exit (see DeviceEngine.run).
                new_stats["fault_word"] = stats["fault_word"] | bits
            return state, sq, new_stats

        return jax.lax.while_loop(cond, body, (state, queue, stats0))

    # -- main loop, placement="devices" -------------------------------------
    def _run_devices(self, state, stq, t_end, max_batches, stats0):
        """The serial super-step under ONE ``shard_map`` over the
        ``"shards"`` mesh (module docstring + DESIGN.md §12).

        Structure per super-step, per device (shard ``my``):

        * **peek** — the plain (non-vmapped) per-shard leg on the local
          squeezed queue, so the rare-path ``lax.cond``s stay conds.
        * **gather heads** — ONE ``all_gather(tiled=True)`` of the
          k-row head slabs; shard-major layout reproduces the serial
          path's ``concatenate`` order exactly.
        * **merge / window / dispatch / global accounting** — computed
          REPLICATED: every device runs the identical deterministic
          computation over the identical gathered slabs, so the
          results agree bitwise without any further collective.
        * **pop / fill** — local: pop my taken prefix, fill with
          ``insert & (dest == my)``.
        * **guard refresh** — collectives cannot appear in the
          while-loop ``cond``, so the replicated guard scalars
          (pending, next time, and the fenced path's global head key)
          ride the carry in ``aux``, recomputed here from one small
          gather of per-shard summaries.

        The global counters ride the carry as a replicated dict ``g``
        and are re-attached to the stacked queue on exit.
        """
        k = self.max_batch_len
        N = self.shards
        num_types = len(self.registry)
        lookaheads = self._lookaheads
        validate_on = self.validate != "off"
        fenced = "bound_t" in stats0
        I32_MAX = jnp.int32(2**31 - 1)

        def _aux_guards(q):
            """Replicated loop-guard scalars from per-shard summaries."""
            hp = jax.lax.all_gather(tiered3_queue_has_pending(q), "shards")
            nt = jax.lax.all_gather(tiered3_queue_next_time(q), "shards")
            aux = {"pending": jnp.any(hp), "next_t": jnp.min(nt)}
            if fenced:
                kt_l, ks_l = tiered3_queue_next_key(q)
                kt = jax.lax.all_gather(kt_l, "shards")
                ks = jax.lax.all_gather(ks_l, "shards")
                nk_t = jnp.min(kt)
                aux["nk_t"] = nk_t
                aux["nk_s"] = jnp.min(jnp.where(kt == nk_t, ks, I32_MAX))
            return aux

        def cond(carry):
            state, q, g, aux, stats = carry
            del state, q
            ok = (
                aux["pending"]
                & (stats["batches"] < max_batches)
                & (aux["next_t"] <= t_end)
            )
            if validate_on:
                ok = ok & (stats["fault_word"] == 0)
            if self.overflow == "error":
                ok = ok & (g["dropped"] == 0)
            if fenced:
                below = (aux["nk_t"] < stats["bound_t"]) | (
                    (aux["nk_t"] == stats["bound_t"])
                    & (aux["nk_s"] < stats["bound_seq"])
                )
                ok = ok & below
            return ok

        def body(carry):
            state, q, g, aux, stats = carry
            del aux
            my = jax.lax.axis_index("shards")

            # 1. local peek, then gather the k-row head slabs.
            # tiled=True concatenates along axis 0 in shard-major
            # order — exactly the serial path's per-shard concatenate.
            q, ts_l, tys_l, args_l, seqs_l = tiered3_queue_peek_front(q, k)
            cts = jax.lax.all_gather(ts_l, "shards", tiled=True)
            ctys = jax.lax.all_gather(tys_l, "shards", tiled=True)
            cargs = jax.lax.all_gather(args_l, "shards", tiled=True)
            cseqs = jax.lax.all_gather(seqs_l, "shards", tiled=True)
            csrc = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)

            # 2. merge + exact global window — replicated, identical
            # to the serial path.
            order = _small_lex_perm(cts, cseqs)[:k]
            ts_c = cts[order]
            tys_c = ctys[order]
            args_c = cargs[order]
            src_c = csrc[order]
            valid = tys_c >= 0
            if fenced:
                seqs_c = cseqs[order]
                valid = valid & (
                    (ts_c < stats["bound_t"])
                    | ((ts_c == stats["bound_t"])
                       & (seqs_c < stats["bound_seq"]))
                )
            la = lookaheads[jnp.clip(tys_c, 0, num_types - 1)]
            wins = jnp.where(valid, ts_c + la, jnp.inf)
            take = window_prefix_mask(ts_c, wins, valid, t_end)
            length = jnp.sum(take).astype(jnp.int32)

            ts = jnp.where(take, ts_c, 0.0)
            tys = jnp.where(take, tys_c, 0)
            args = jnp.where(take[:, None], args_c, 0.0)

            # 3. pop MY taken prefix (local).
            q = tiered3_queue_pop_prefix(
                q, jnp.sum(take & (src_c == my)).astype(jnp.int32), k,
            )

            # 4. dispatch: the parent's composed-batch path, computed
            # redundantly on every device (deterministic → replicated).
            state, emits = self._dispatch_window(state, ts, tys, args,
                                                 length)

            # 5. global seq + overflow accounting (replicated).
            ty_r = emits[:, 1].astype(jnp.int32)
            valid_r = ty_r >= 0
            vrank = _prefix_rank(valid_r)
            num_valid = jnp.sum(valid_r).astype(jnp.int32)
            size_mid = g["size"] - length
            insert = valid_r & (size_mid + vrank < self.capacity)
            num_insert = jnp.sum(insert).astype(jnp.int32)
            seq_r = g["next_seq"] + vrank

            # 6. exchange: every device holds the full replicated emit
            # block, so routing needs no collective — fill MY rows.
            dest = self._shard_of(ty_r, emits[:, 2:])
            q = tiered3_queue_fill_rows_tagged(
                q, emits, seq_r, insert & (dest == my),
                kernels=self.queue_kernels,
            )

            g = {
                "size": size_mid + num_valid,
                "next_seq": g["next_seq"] + num_valid,
                "dropped": g["dropped"] + (num_valid - num_insert),
            }
            last_t = ts[jnp.maximum(length - 1, 0)]
            prev_time = stats["time"]
            new_stats = {
                "batches": stats["batches"] + 1,
                "events": stats["events"] + length,
                "emitted": stats["emitted"] + num_valid,
                "time": jnp.maximum(stats["time"], last_t),
            }
            self._count_window(stats, new_stats, tys, length)
            if fenced:
                new_stats["bound_t"] = stats["bound_t"]
                new_stats["bound_seq"] = stats["bound_seq"]
            if validate_on:
                bits_all = jax.lax.all_gather(
                    _validate.tiered3_fault_bits(q, local=True), "shards")
                occ_all = jax.lax.all_gather(
                    tiered3_queue_occupancy(q), "shards")
                bits = jnp.int32(0)
                for i in range(N):
                    bits = bits | bits_all[i]
                total_occ = jnp.sum(occ_all).astype(jnp.int32)
                bits = bits | _validate._bit(
                    total_occ + g["dropped"] != g["size"],
                    _validate.FAULT_CONSERVATION,
                )
                bits = bits | jnp.where(
                    (length > 0) & (ts[0] < prev_time),
                    jnp.int32(FAULT_CLOCK), jnp.int32(0),
                )
                new_stats["fault_word"] = stats["fault_word"] | bits
            return state, q, g, _aux_guards(q), new_stats

        def mapped(state, stq, t_end_, max_batches_, stats0_):
            del t_end_, max_batches_  # closed over (replicated scalars)
            # Each device's block of the stacked queue has leading
            # extent 1 — squeeze to the plain per-shard queue so every
            # queue op below is the serial path's op, conds included.
            local_q = jax.tree_util.tree_map(lambda x: x[0], stq.q)
            g0 = {"size": stq.size, "next_seq": stq.next_seq,
                  "dropped": stq.dropped}
            carry = (state, local_q, g0, _aux_guards(local_q), stats0_)
            state_f, q_f, g_f, _aux_f, stats_f = jax.lax.while_loop(
                cond, body, carry)
            out = StackedShardedQueue(
                q=jax.tree_util.tree_map(lambda x: x[None], q_f),
                size=g_f["size"], next_seq=g_f["next_seq"],
                dropped=g_f["dropped"],
            )
            return state_f, out, stats_f

        qspec = StackedShardedQueue(q=P("shards"), size=P(),
                                    next_seq=P(), dropped=P())
        run = jax.shard_map(
            mapped, mesh=self._mesh,
            in_specs=(P(), qspec, P(), P(), P()),
            out_specs=(P(), qspec, P()),
            # state/stats/counters are replicated by construction
            # (identical deterministic compute per device); rep
            # checking cannot see through the redundant dispatch.
            check_vma=False,
        )
        return run(state, stq, t_end, max_batches, stats0)
