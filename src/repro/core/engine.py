"""Host-runtime facade and the fully on-device DES engine.

This is the BACKEND layer: models should be defined once with
:class:`repro.api.SimProgram` and compiled here via
``prog.build(backend=..., ...)`` (DESIGN.md §1.1) — both classes below
expose ``from_program`` constructors for that path.  Direct
construction remains supported for benchmarks and tests that probe one
runtime mechanism.

Two runtimes (DESIGN.md §2):

* **Host runtime** (paper-faithful): :class:`Simulator` drives a Python
  event loop over a binary heap, dispatching pre-composed jitted batch
  programs — the direct analogue of the paper's function-pointer
  dispatch.

* **Device runtime** (TPU-native adaptation): :class:`DeviceEngine`
  compiles the ENTIRE simulation — queue, lookahead-window extraction,
  Horner encoding, batch dispatch — into one XLA program built around
  ``lax.while_loop`` + ``lax.switch``.  Every composed batch body is a
  contiguous fragment inside that module, so XLA applies cross-event
  optimization exactly as clang does in the paper, and there are zero
  host round-trips during the run.

Per-batch scheduling cost is selected by ``queue_mode`` (DESIGN.md §4):

* ``"tiered3"`` (default) — the log-structured third tier (DESIGN.md
  §4.4): staging flushes become bounded sorted runs and front refills
  a bounded k-way merge, so no per-batch path is O(capacity) even at
  >=90% occupancy; the one O(capacity) compaction amortizes over an
  entire run pool.  Serves every regime including near-full 64k+
  scenarios, which is why it is the default (promoted after soaking in
  the serving scenarios since PR 4).
* ``"tiered"`` — two-tier queue; per-batch work touches only the small
  front/staging tiers, so scheduling overhead is independent of queue
  capacity on the common path (the staging flush merge is still
  O(capacity) under near-full, near-head re-emit pressure).
* ``"flat"`` — the PR-1 single-array vectorized ops: a constant number
  of data-parallel passes, but the emit merge is O(capacity) per batch.
* ``"reference"`` — seed semantics for differential testing and the
  overhead benchmark: extraction is the serial per-event argmin chain
  (the executable spec), inserts the one-pass
  :func:`device_queue_push_rows` (bit-identical to the serial seed
  pushes INCLUDING slot placement; the serial chain survives as
  ``device_queue_push_rows_serial``, exercised by the differential
  tests).

The queue argument to :meth:`DeviceEngine.run` is DONATED to the jitted
program (its buffers are reused for the output queue), so a queue value
must not be reused after being passed to ``run`` — rebuild it with
:meth:`DeviceEngine.initial_queue` or use the returned queue.

Single-type-run windows can additionally bypass the sequential switch
branch: event types listed in ``entity_handlers`` are dispatched through
``vmap`` over entity slices of the state
(:func:`repro.core.vectorize.make_masked_run_handler`) — the
serving-style data-parallel win, now available on the device engine.

On-device emit convention: handlers marked with ``@emits_events`` return
``(state, emits)`` with ``emits: f32[max_emit, 2 + ARG_WIDTH]`` rows of
``(absolute_time, type, arg...)``; ``type == -1`` marks unused slots.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codec import DenseCodec, PaperCodec, make_codec
from repro.core.composer import (
    WORD_COUNT_LIMIT,
    EagerComposer,
    LazyComposer,
    _emit_layout,
    build_fused_dispatcher,
    build_masked_dispatcher,
    build_switch_dispatcher,
    default_dispatch_mode,
)
from repro.core.events import ARG_WIDTH, EventRegistry
from repro.core.queue import (
    DeviceQueue,
    HostEventQueue,
    Tiered3DeviceQueue,
    TieredDeviceQueue,
    _prefix_rank,
    device_queue_extract,
    device_queue_extract_ref,
    device_queue_fill_rows,
    device_queue_from_host,
    device_queue_next_time,
    device_queue_next_time_ref,
    device_queue_push_rows,
    tiered3_queue_absorb_rows,
    tiered3_queue_extract,
    tiered3_queue_fill_rows,
    tiered3_queue_fill_rows_tagged,
    tiered3_queue_from_host,
    tiered3_queue_has_pending,
    tiered3_queue_next_key,
    tiered3_queue_next_time,
    tiered3_queue_occupancy,
    tiered_queue_extract,
    tiered_queue_fill_rows,
    tiered_queue_from_host,
    tiered_queue_has_pending,
    tiered_queue_next_time,
    tiered_queue_occupancy,
)
from repro.core import validate as _validate
from repro.core.spans import DISPATCH, EXTRACT, INSERT
from repro.core.validate import FAULT_CLOCK, FAULT_OVERFLOW, EngineFaultError
from repro.core.scheduler import (
    ConservativeScheduler,
    RunStats,
    SpeculativeScheduler,
    run_unbatched,
)
from repro.core.vectorize import make_masked_run_handler


class BoundaryRead(NamedTuple):
    """What the segment driver reads of the queue and the run stats at
    a segment boundary (:meth:`DeviceEngine.boundary_read`)."""

    batches: int       # cumulative super-steps in the stats
    spill_n: int       # rows in the stats' spill buffer (0 unbuilt)
    occupancy: int     # real pending events in the queue
    next_time: float   # earliest pending timestamp (inf when empty)


class Simulator:
    """Host-runtime facade over registry + queue + scheduler.

    Backend layer: prefer defining models once with
    :class:`repro.api.SimProgram` and compiling via
    ``prog.build(backend="host", ...)`` — the same definition then also
    runs on the device engine.
    """

    @classmethod
    def from_program(cls, program, *, composer: str = "lazy",
                     state_spec=None, arg_spec=None) -> "Simulator":
        """Construct the host backend from a frozen SimProgram, with the
        program's scheduled initial events already queued."""
        cfg = program.config
        sim = cls(
            program.host_registry(),
            max_batch_len=cfg.max_batch_len,
            codec=cfg.codec,
            composer=composer,
            state_spec=state_spec,
            arg_spec=arg_spec,
        )
        for (t, type_id, arg) in program.scheduled_events():
            sim.queue.push(t, type_id, arg)
        return sim

    def __init__(self, registry: EventRegistry, *, max_batch_len: int = 4,
                 codec: str = "dense", composer: str = "lazy",
                 state_spec=None, arg_spec=None):
        registry.freeze()
        self.registry = registry
        self.codec = make_codec(codec, len(registry), max_batch_len)
        if composer == "lazy":
            self.composer = LazyComposer(registry, self.codec)
        elif composer == "eager":
            self.composer = EagerComposer(
                registry, self.codec, state_spec=state_spec, arg_spec=arg_spec
            )
        else:
            raise ValueError(f"unknown composer {composer!r}")
        self.queue = HostEventQueue()

    def schedule(self, time: float, type_name: str, arg: Any = None):
        et = self.registry[type_name]
        return self.queue.push(time, et.type_id, arg)

    def run(self, state, *, mode: str = "conservative",
            max_events: int | None = None) -> tuple[Any, RunStats]:
        if mode == "conservative":
            sched = ConservativeScheduler(self.registry, self.composer)
            return sched.run(state, self.queue, max_events=max_events)
        if mode == "speculative":
            sched = SpeculativeScheduler(self.registry, self.composer)
            return sched.run(state, self.queue, max_events=max_events)
        if mode == "unbatched":
            return run_unbatched(
                self.registry, state, self.queue, max_events=max_events
            )
        raise ValueError(f"unknown mode {mode!r}")


# ---------------------------------------------------------------------------
# On-device engine
# ---------------------------------------------------------------------------

# Default hot-set width for dispatch_mode="fused" without declared
# hot_words.
_DEFAULT_HOT_W = 32


@dataclasses.dataclass
class DeviceEngine:
    """Builder for the single-program on-device simulation.

    Preferred entry point: ``repro.api.SimProgram.build(
    backend="device", ...)``, which constructs this class via
    :meth:`from_program` and wraps the run/queue lifecycle in a
    re-runnable ``CompiledSim``.  Direct usage::

        eng = DeviceEngine(registry, max_batch_len=4, capacity=1024)
        queue = eng.initial_queue([(t, type_id, arg_vec), ...])
        final_state, final_queue, stats = eng.run(state0, queue,
                                                  max_batches=10_000)

    ``eng.run`` is jitted once; repeat calls with same-shaped inputs are
    pure device execution.  The queue argument is donated (consumed) —
    build a fresh one per run or chain the returned queue.  Run stats
    include ``dropped``, the number of emitted events lost to
    queue-capacity overflow.

    ``queue_mode`` selects the pending-set implementation:
    ``"tiered3"`` (default: log-structured run tier with bounded
    worst-case per-batch cost at any occupancy/capacity),
    ``"tiered"`` (two-tier: capacity-independent per-batch cost on the
    common path only), ``"flat"`` (PR-1 single-array vectorized ops),
    or ``"reference"`` (seed semantics: serial-spec extraction + the
    bit-identical one-pass bulk insert).  For multi-queue execution
    see :class:`repro.core.sharded.ShardedDeviceEngine`.
    ``front_cap``/``stage_cap`` size the tiered queues' front tier and
    staging ring and ``num_runs`` the tiered3 run pool; the defaults
    scale with ``max_batch_len`` and ``max_emit`` and are clamped to
    valid ranges.

    ``dispatch_mode`` selects how an extracted window reaches its
    handlers (DESIGN.md §7; all three are bit-identical).  Left
    ``None``, the word count picks it (:func:`~repro.core.composer.
    default_dispatch_mode`): ``"switch"`` up to
    :data:`~repro.core.composer.WORD_COUNT_LIMIT` words, else
    ``"masked"``; ``self.dispatch_mode`` then holds the choice.

    * ``"switch"`` — one ``lax.switch`` over ALL composed batch words;
      maximal cross-event scope, compile cost Σ Tᵏ.  Naming it for
      more words than the limit raises at once.
    * ``"masked"`` — the generic per-lane masked path (per-handler
      scope; O(T·max_batch_len) compile, no cross-event optimization).
    * ``"fused"`` — two-level: the top-W *hot* words (``hot_words``,
      or a profiled histogram via
      :func:`repro.core.composer.hot_words_from_counts`; default: the
      first ``32`` dense codes) run as straight-line super-procedures
      behind a bounded W+1-way switch, everything else falls back to
      the masked path.  W-linear compile, hot windows keep the full
      cross-event scope.

    ``queue_kernels`` selects the tiered3 front-tier hot-loop
    implementation: ``"xla"`` (default — the all-pairs-rank + gather
    shapes tuned for XLA:CPU) or ``"pallas"`` (Pallas kernels in
    ``repro.kernels.queue_front`` keeping the window extract and the
    front counting-merge in VMEM; interpret mode on CPU, bit-identical
    output, requires ``queue_mode="tiered3"``).

    ``entity_handlers`` maps a type_id to an entity-local handler
    ``(entity_state, t, arg) -> entity_state`` over slices of the state
    pytree (leading axis = entity).  When an extracted window is a
    single-type run of such a type, the engine dispatches it as one
    ``vmap`` over the touched entities (``arg[0]`` is the entity index)
    instead of the sequential switch branch.  The registered sequential
    handler must match the local handler's semantics — it still serves
    mixed windows.  Entity-parallel types must not emit events, and a
    window must not contain two events for the same entity.
    """

    registry: EventRegistry
    max_batch_len: int = 4
    capacity: int = 1024
    max_emit: int = 2
    t_end: float = float("inf")
    queue_mode: str = "tiered3"
    front_cap: int | None = None
    stage_cap: int | None = None
    num_runs: int | None = None
    dispatch_mode: str | None = None
    hot_words: Any = None
    queue_kernels: str = "xla"
    entity_handlers: Mapping[int, Callable] | None = None
    validate: str = "off"
    overflow: str = "drop"
    # Removed 2024-era flag; kept as an InitVar so old call sites get a
    # pointer at queue_mode instead of a generic unexpected-kwarg error.
    use_vectorized_queue: dataclasses.InitVar[Any] = None

    def __post_init__(self, use_vectorized_queue):
        if use_vectorized_queue is not None:
            raise TypeError(
                "DeviceEngine(use_vectorized_queue=...) was removed; "
                "pass queue_mode='flat' (True) or queue_mode="
                "'reference' (False) instead — or build through "
                "repro.api.SimProgram.build(backend='device', "
                "queue_mode=...)."
            )
        self.registry.freeze()
        if self.queue_mode not in ("tiered", "tiered3", "flat",
                                   "reference"):
            raise ValueError(
                f"unknown queue_mode {self.queue_mode!r}; expected "
                "'tiered', 'tiered3', 'flat', or 'reference'"
            )
        if self.dispatch_mode is None:
            self.dispatch_mode = default_dispatch_mode(
                len(self.registry), self.max_batch_len)
        if self.dispatch_mode not in ("switch", "masked", "fused"):
            raise ValueError(
                f"unknown dispatch_mode {self.dispatch_mode!r}; expected "
                "'switch', 'masked', or 'fused'"
            )
        if self.hot_words is not None and self.dispatch_mode != "fused":
            raise ValueError(
                "hot_words only applies to dispatch_mode='fused' "
                f"(got dispatch_mode={self.dispatch_mode!r})"
            )
        if self.queue_kernels not in ("xla", "pallas"):
            raise ValueError(
                f"unknown queue_kernels {self.queue_kernels!r}; expected "
                "'xla' or 'pallas'"
            )
        if self.queue_kernels == "pallas" and self.queue_mode != "tiered3":
            raise ValueError(
                "queue_kernels='pallas' requires queue_mode='tiered3' "
                f"(got {self.queue_mode!r}): the Pallas kernels implement "
                "the tiered3 front-tier hot loops"
            )
        if self.validate not in ("off", "cheap", "full"):
            raise ValueError(
                f"unknown validate {self.validate!r}; expected "
                "'off', 'cheap', or 'full'"
            )
        if self.overflow not in ("drop", "error", "spill"):
            raise ValueError(
                f"unknown overflow {self.overflow!r}; expected "
                "'drop', 'error', or 'spill'"
            )
        if self.overflow == "spill" and self.queue_mode != "tiered3":
            raise ValueError(
                "overflow='spill' requires queue_mode='tiered3' (got "
                f"{self.queue_mode!r}): spilled rows reabsorb through "
                "the tiered3 tagged-fill path"
            )
        if self.overflow == "spill" and self.queue_kernels != "xla":
            raise ValueError(
                "overflow='spill' requires queue_kernels='xla': the "
                "lex-bounded extraction fence is XLA-only"
            )
        # Tier sizing: the rare O(capacity) paths (front refill, staging
        # flush) amortize over ~front_cap/max_batch_len resp.
        # ~stage_cap/emit_rows batches, so both tiers default to many
        # multiples of the per-batch quanta.
        emit_rows = self.max_batch_len * self.max_emit
        if self.front_cap is None:
            self.front_cap = max(256, 8 * self.max_batch_len)
        self.front_cap = min(max(self.front_cap, self.max_batch_len),
                             self.capacity)
        if self.stage_cap is None:
            self.stage_cap = max(256, 8 * emit_rows)
        self.stage_cap = max(self.stage_cap, emit_rows)
        # Run pool: one compaction per num_runs*stage_cap staged events.
        if self.num_runs is None:
            self.num_runs = 8
        self.num_runs = max(self.num_runs, 1)
        self.codec = DenseCodec(len(self.registry), self.max_batch_len)
        self._empty_emits = _emit_layout(self.max_batch_len,
                                         self.max_emit)[2]
        # Only the chosen dispatcher is built: the full switch's
        # branches number Σ Tᵏ.
        if self.dispatch_mode == "switch":
            self.dispatch = build_switch_dispatcher(
                self.registry, self.codec, max_emit=self.max_emit
            )
        elif self.dispatch_mode == "masked":
            self.dispatch = build_masked_dispatcher(
                self.registry, self.codec, max_emit=self.max_emit
            )
        else:
            hot = self.hot_words
            if hot is None:
                # No profile declared: bake the first W dense codes
                # (shortest words first — deterministic, and small
                # alphabets degenerate to the full switch).  Real
                # deployments should pass profiled hot_words
                # (composer.hot_words_from_counts over a prior run's
                # ``word_counts``).
                hot = [
                    self.codec.decode(c)
                    for c in range(min(self.codec.num_batches,
                                       _DEFAULT_HOT_W))
                ]
            self.dispatch = build_fused_dispatcher(
                self.registry, self.codec, hot, max_emit=self.max_emit
            )
            self.hot_words = self.dispatch.hot_words
        # Per-word batch histogram in the run stats (hot-word profiling
        # + benchmarks/batch_counts.py), gated so a large alphabet
        # cannot blow up the while-loop carry.
        self._track_word_counts = (
            self.codec.num_batches <= WORD_COUNT_LIMIT
        )
        self._lookaheads = self.registry.lookaheads()
        if self.entity_handlers:
            entity_types = sorted(self.entity_handlers)
            for ty in entity_types:
                if not 0 <= ty < len(self.registry):
                    raise ValueError(
                        f"entity_handlers key {ty} is not a registered "
                        f"type id (registry has {len(self.registry)} types)"
                    )
                if self.registry[ty].returns_events:
                    raise ValueError(
                        f"entity-parallel type {self.registry[ty].name!r} "
                        "must not emit events"
                    )
            branch_of_type = [-1] * len(self.registry)
            for i, ty in enumerate(entity_types):
                branch_of_type[ty] = i
            self._run_branch_of_type = jnp.asarray(branch_of_type, jnp.int32)
            self._run_branches = [
                make_masked_run_handler(self.entity_handlers[ty])
                for ty in entity_types
            ]
        else:
            self._run_branch_of_type = None
            self._run_branches = []
        # The queue (arg 1) is donated: repeat runs reuse its
        # capacity-sized buffers in place instead of copying them.  The
        # state is NOT donated — callers routinely feed one initial
        # state to several engines (and donation of a shared buffer
        # would poison the caller's copy).  `max_batches` and the stats
        # carry are TRACED arguments: segmented execution re-enters the
        # same compiled loop with a new cumulative batch target and the
        # previous segment's stats, so checkpoint cadence never forces
        # a recompile.
        self._run_jit = jax.jit(self._run, donate_argnums=(1,))

    @classmethod
    def from_program(cls, program, *, queue_mode: str = "tiered3",
                     capacity: int | None = None,
                     front_cap: int | None = None,
                     stage_cap: int | None = None,
                     num_runs: int | None = None,
                     dispatch_mode: str | None = None,
                     hot_words=None,
                     queue_kernels: str = "xla",
                     validate: str = "off",
                     overflow: str = "drop",
                     t_end: float = float("inf")) -> "DeviceEngine":
        """Construct the device backend from a frozen SimProgram.

        The program supplies the adapted registry (delay-relative emits
        rewritten to the absolute-time on-device convention), the
        entity-parallel dispatch table, and the shared Config knobs;
        per-backend kwargs stay here.  ``max_emit`` intentionally has
        no override: the program's handler adapters bake the emit-row
        shape from ``Config.max_emit``, so a differing engine width
        could never run.
        """
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
        )

    # -- queue construction -------------------------------------------------
    def initial_queue(
        self, events
    ) -> DeviceQueue | TieredDeviceQueue | Tiered3DeviceQueue:
        # Built host-side, one device_put (None args become zero vectors).
        if self.queue_mode == "tiered":
            return tiered_queue_from_host(
                events, self.capacity, front_cap=self.front_cap,
                stage_cap=self.stage_cap,
            )
        if self.queue_mode == "tiered3":
            return tiered3_queue_from_host(
                events, self.capacity, front_cap=self.front_cap,
                stage_cap=self.stage_cap, num_runs=self.num_runs,
            )
        return device_queue_from_host(events, self.capacity)

    def initial_queue_spill(self, events):
        """Seed split for ``overflow='spill'``: the lex-earliest
        ``capacity`` events seed the queue with their original
        input-order seqs; the rest start life in the host spill pool
        (instead of being dropped as ghosts).  Returns ``(queue,
        spill_rows, spill_seqs)`` — the rows in device emit layout
        ``(time, type, arg...)``, ready for
        :func:`tiered3_queue_absorb_rows`.
        """
        if self.queue_mode != "tiered3":
            raise ValueError("overflow='spill' requires queue_mode='tiered3'")
        events = list(events)
        n = len(events)
        if n <= self.capacity:
            return (self.initial_queue(events),
                    np.zeros((0, 2 + ARG_WIDTH), np.float32),
                    np.zeros((0,), np.int32))
        order = sorted(range(n), key=lambda i: (float(events[i][0]), i))
        keep = sorted(order[:self.capacity])
        spill = sorted(order[self.capacity:])
        q = tiered3_queue_from_host(
            [events[i] for i in keep], self.capacity,
            front_cap=self.front_cap, stage_cap=self.stage_cap,
            num_runs=self.num_runs, seqs=keep,
        )
        # Spilled events own seqs too: the counter must already be past
        # every seed seq, queued or spilled.
        q = q._replace(next_seq=jnp.int32(n))
        rows = np.zeros((len(spill), 2 + ARG_WIDTH), np.float32)
        for j, i in enumerate(spill):
            t, ty, arg = events[i]
            rows[j, 0] = t
            rows[j, 1] = ty
            if arg is not None:
                rows[j, 2:] = np.asarray(arg, np.float32)
        return q, rows, np.asarray(spill, np.int32)

    # -- extraction (paper Fig 2) --------------------------------------------
    @jax.named_scope(EXTRACT)
    def _extract(self, queue, t_cap=None, bound=None):
        if self.queue_mode == "tiered":
            return tiered_queue_extract(
                queue, self.max_batch_len, self._lookaheads, t_cap
            )
        if self.queue_mode == "tiered3":
            return tiered3_queue_extract(
                queue, self.max_batch_len, self._lookaheads, t_cap,
                kernels=self.queue_kernels, bound=bound,
            )
        if self.queue_mode == "flat":
            return device_queue_extract(
                queue, self.max_batch_len, self._lookaheads, t_cap
            )
        return device_queue_extract_ref(
            queue, self.max_batch_len, self._lookaheads, t_cap
        )

    # -- dispatch -------------------------------------------------------------
    @jax.named_scope(DISPATCH)
    def _dispatch_window(self, state, ts, tys, args, length):
        """Dispatch one extracted window; returns (state, emits).

        The composed path is selected by ``dispatch_mode``; all three
        execute the identical handler sequence for any window, so the
        choice never changes results (parity-pinned).
        """
        def switch_path(state):
            if self.dispatch_mode == "masked":
                return self.dispatch(state, ts, tys, args, length)
            code = self.codec.encode_jnp(tys, length)
            if self.dispatch_mode == "fused":
                return self.dispatch(code, state, ts, tys, args, length)
            return self.dispatch(code, state, ts, tys, args)

        if not self._run_branches:
            return switch_path(state)

        lane = jnp.arange(self.max_batch_len)
        in_window = lane < length
        branch = self._run_branch_of_type[
            jnp.clip(tys[0], 0, len(self.registry) - 1)
        ]
        is_run = (
            (length > 0)
            & (branch >= 0)
            & jnp.all(jnp.where(in_window, tys == tys[0], True))
        )

        def run_path(state):
            entity_ids = args[:, 0].astype(jnp.int32)
            state = jax.lax.switch(
                jnp.maximum(branch, 0), self._run_branches,
                state, ts, args, entity_ids, in_window,
            )
            return state, self._empty_emits()

        return jax.lax.cond(is_run, run_path, switch_path, state)

    # -- run accounting -------------------------------------------------------
    def initial_run_stats(self):
        """The stats carry threaded through the while-loop.

        Segmented execution hands the PREVIOUS segment's stats back in,
        so cumulative counters (``batches``, ``events``, ``emitted``,
        ``time``, the fault word, the spill buffer) survive segment
        boundaries and a segmented run is bit-identical to an
        unsegmented one by construction.
        """
        stats = {
            "batches": jnp.int32(0),
            "events": jnp.int32(0),
            "emitted": jnp.int32(0),
            "time": jnp.float32(0.0),
            "type_counts": jnp.zeros((len(self.registry),), jnp.int32),
        }
        if self._track_word_counts:
            stats["word_counts"] = jnp.zeros(
                (self.codec.num_batches,), jnp.int32
            )
        if self.validate != "off":
            # Only the WORD rides the carry.  The faulting step is not
            # tracked on device: a set bit freezes the loop guard, so
            # at exit the step is recoverable from ``batches`` alone
            # (see ``run``) — one fewer carried scalar, which matters
            # because every extra carry leaf is another launch-bound
            # copy/fusion kernel per super-step on CPU.
            stats["fault_word"] = jnp.int32(0)
        if self.overflow == "spill":
            rows = self._empty_emits()
            stats["spill_rows"] = jnp.asarray(rows)
            stats["spill_seqs"] = jnp.zeros((rows.shape[0],), jnp.int32)
            stats["spill_n"] = jnp.int32(0)
            stats["bound_t"] = jnp.float32(jnp.inf)
            stats["bound_seq"] = jnp.int32(2**31 - 1)
        return stats

    def _count_window(self, stats, new_stats, tys, length):
        """Add one window to the histograms the stats carry:
        ``type_counts`` (events by type, every build) and
        ``word_counts`` (batches by Horner word, while the code space
        is at most ``WORD_COUNT_LIMIT``; XLA CSEs the encode against
        the switch path's)."""
        lane = jnp.arange(self.max_batch_len) < length
        hit = (tys[:, None] == jnp.arange(len(self.registry))) & lane[:, None]
        new_stats["type_counts"] = (
            stats["type_counts"] + jnp.sum(hit, axis=0, dtype=jnp.int32))
        if self._track_word_counts:
            code = self.codec.encode_jnp(tys, length)
            new_stats["word_counts"] = stats["word_counts"].at[code].add(1)

    def place_queue(self, queue):
        """Re-place a restored queue's leaves for this engine.  The
        single-queue engines run on the default device — identity;
        sharded ``placement="devices"`` overrides this to re-shard
        onto its mesh (restores land everything on one device)."""
        return queue

    def queue_occupancy(self, queue):
        """Real pending-event count (conservation-law accounting)."""
        if self.queue_mode == "tiered3":
            return tiered3_queue_occupancy(queue)
        if self.queue_mode == "tiered":
            return tiered_queue_occupancy(queue)
        return jnp.sum(queue.types >= 0).astype(jnp.int32)

    def queue_next_time(self, queue):
        """Earliest pending timestamp (``inf`` when empty)."""
        if self.queue_mode == "tiered3":
            return tiered3_queue_next_time(queue)
        if self.queue_mode == "tiered":
            return tiered_queue_next_time(queue)
        if self.queue_mode == "flat":
            return device_queue_next_time(queue)
        return device_queue_next_time_ref(queue)

    def _boundary_read(self, queue, counters):
        # One i32[4] row, so the host pulls a single buffer; the time
        # rides bitcast.
        return jnp.stack([
            counters["batches"],
            counters.get("spill_n", jnp.int32(0)),
            self.queue_occupancy(queue),
            jax.lax.bitcast_convert_type(
                self.queue_next_time(queue), jnp.int32),
        ])

    def boundary_read(self, queue, stats=None) -> BoundaryRead:
        """Batch count, spill count, occupancy and next time in ONE
        compiled call and one device-to-host transfer.

        The segment driver needs all four at every boundary; computed
        eagerly they are ~30 small ops, each its own dispatch, and four
        read-backs.  ``stats=None`` reads the initial stats' counters.
        Jitted once per engine and cached, like the entry audit."""
        fn = self.__dict__.get("_boundary_read_jit")
        if fn is None:
            fn = jax.jit(self._boundary_read)
            self._boundary_read_jit = fn
        src = self.initial_run_stats() if stats is None else stats
        counters = {k: src[k] for k in ("batches", "spill_n") if k in src}
        row = np.asarray(fn(queue, counters))
        return BoundaryRead(int(row[0]), int(row[1]), int(row[2]),
                            float(row[3:].view(np.float32)[0]))

    def absorb_rows(self, queue, rows, seqs, insert):
        """Absorb externally keyed rows (stream arrivals) where
        ``insert`` is set.  Caller guarantees the masked rows fit;
        seqs come from the run's reserved arrival range (DESIGN.md
        §10), so absorbed rows land at their pre-seeded lex rank."""
        if self.queue_mode != "tiered3":
            raise ValueError(
                f"absorb_rows requires queue_mode='tiered3', got "
                f"{self.queue_mode!r}"
            )
        return tiered3_queue_absorb_rows(queue, rows, seqs, insert=insert)

    def _cheap_fault_bits(self, queue):
        """O(front) per-super-step invariant bits for this queue mode."""
        if self.queue_mode == "tiered3":
            return _validate.tiered3_fault_bits(
                queue, local=(self.overflow == "spill")
            )
        if self.queue_mode == "tiered":
            return _validate.tiered_fault_bits(queue)
        if self.queue_mode == "flat":
            return _validate.flat_fault_bits(queue, sorted_layout=True)
        return _validate.flat_fault_bits(queue, sorted_layout=False)

    def _spill_insert(self, queue, emits, stats):
        """Insert the emit rows that fit; divert the rest to the
        host-bound spill buffer carried in the stats.

        Every valid row — queued or spilled — draws its seq from the
        one global counter, so a reabsorbed row keeps its exact place
        in the total ``(time, seq)`` order.  Returns ``(queue, delta)``
        with ``delta`` the spill-related stats updates.  The loop guard
        stops the segment as soon as ``spill_n > 0``, so at most one
        batch ever writes the buffer before the host drains it.
        """
        R = emits.shape[0]
        valid = emits[:, 1] >= 0
        vrank = _prefix_rank(valid)
        num_valid = jnp.sum(valid).astype(jnp.int32)
        base_seq = queue.next_seq
        seq_r = base_seq + vrank
        occ = tiered3_queue_occupancy(queue)
        fits = valid & (occ + vrank < jnp.int32(self.capacity))
        spilled = valid & ~fits
        queue = tiered3_queue_fill_rows_tagged(
            queue, emits, seq_r, fits, kernels=self.queue_kernels
        )
        # The tagged fill advances next_seq only past INSERTED rows;
        # spilled rows still own theirs.
        queue = queue._replace(next_seq=base_seq + num_valid)
        srank = _prefix_rank(spilled)
        dst = jnp.where(spilled, srank, jnp.int32(R))
        n_spill = jnp.sum(spilled).astype(jnp.int32)
        s_t = jnp.where(spilled, emits[:, 0], jnp.inf)
        min_t = jnp.min(s_t)
        min_s = jnp.min(jnp.where(
            spilled & (emits[:, 0] == min_t), seq_r, jnp.int32(2**31 - 1)
        ))
        # Tighten the execution fence to the lex-earliest outstanding
        # spilled key: nothing at or past it may run before reabsorb.
        take = (min_t < stats["bound_t"]) | (
            (min_t == stats["bound_t"]) & (min_s < stats["bound_seq"])
        )
        delta = {
            "spill_rows": stats["spill_rows"].at[dst].set(
                emits, mode="drop"
            ),
            "spill_seqs": stats["spill_seqs"].at[dst].set(
                seq_r, mode="drop"
            ),
            "spill_n": stats["spill_n"] + n_spill,
            "bound_t": jnp.where(take, min_t, stats["bound_t"]),
            "bound_seq": jnp.where(take, min_s, stats["bound_seq"]),
        }
        return queue, delta

    # -- main loop ------------------------------------------------------------
    def _run(self, state, queue, t_end, max_batches, stats0):
        inserts = {
            "tiered": tiered_queue_fill_rows,
            "tiered3": lambda q, rows: tiered3_queue_fill_rows(
                q, rows, kernels=self.queue_kernels
            ),
            "flat": device_queue_fill_rows,
            "reference": device_queue_push_rows,
        }
        insert = inserts[self.queue_mode]

        # Loop while events are actually pending.  `queue.size` alone is
        # wrong here: it counts overflow-dropped ghosts, which would spin
        # the loop forever on an empty queue after an overflow.  The
        # tiered check is refill-aware (the front may be empty while
        # staging/main still hold events); under the canonical sorted
        # layout the head slot answers in O(1); the reference layout
        # needs the full occupancy mask.
        if self.queue_mode == "tiered":
            has_pending = tiered_queue_has_pending
            next_time = tiered_queue_next_time
        elif self.queue_mode == "tiered3":
            has_pending = tiered3_queue_has_pending
            next_time = tiered3_queue_next_time
        elif self.queue_mode == "flat":
            has_pending = lambda queue: queue.types[0] >= 0
            next_time = device_queue_next_time
        else:
            has_pending = lambda queue: jnp.any(queue.types >= 0)
            next_time = device_queue_next_time_ref

        # `t_end` is a traced value, so one compiled program serves every
        # horizon.  The contract (shared with the host schedulers): the
        # dynamic extraction window is capped at t_end, so exactly the
        # events with timestamp <= t_end execute — later ones stay
        # queued — identically on every backend.  `max_batches` is
        # cumulative against the carried stats, which is what makes a
        # segmented run re-enter this loop mid-count.
        validate_on = self.validate != "off"
        spill = self.overflow == "spill"
        # The admission fence: nothing at or past the lex-earliest
        # OUTSTANDING external key — a spilled row awaiting reabsorb,
        # or the next unabsorbed stream arrival — may execute.  Spill
        # mode always carries the bound; a streamed run injects
        # ``bound_t``/``bound_seq`` into the incoming stats, and the
        # carry STRUCTURE is part of the jit cache key, so closed runs
        # compile a fence-free loop at zero cost.
        fenced = spill or "bound_t" in stats0
        if fenced and self.queue_mode != "tiered3":
            raise ValueError(
                "the admission fence (overflow='spill' / streamed "
                "arrivals) requires queue_mode='tiered3', got "
                f"{self.queue_mode!r}"
            )

        def cond(carry):
            state, queue, stats = carry
            del state
            ok = (
                has_pending(queue)
                & (stats["batches"] < max_batches)
                & (next_time(queue) <= t_end)
            )
            if validate_on:
                # Fail-fast without host sync: a set bit freezes the
                # loop at the faulting super-step.
                ok = ok & (stats["fault_word"] == 0)
            if self.overflow == "error":
                ok = ok & (queue.dropped == 0)
            if fenced:
                nk_t, nk_s = tiered3_queue_next_key(queue)
                below = (nk_t < stats["bound_t"]) | (
                    (nk_t == stats["bound_t"])
                    & (nk_s < stats["bound_seq"])
                )
                ok = ok & below
            if spill:
                ok = ok & (stats["spill_n"] == 0)
            return ok

        def body(carry):
            state, queue, stats = carry
            if fenced:
                queue, ts, tys, args, length = self._extract(
                    queue, t_end,
                    bound=(stats["bound_t"], stats["bound_seq"]),
                )
            else:
                queue, ts, tys, args, length = self._extract(queue, t_end)
            prev_time = stats["time"]
            state, emits = self._dispatch_window(state, ts, tys, args, length)
            with jax.named_scope(INSERT):
                if spill:
                    queue, spill_delta = self._spill_insert(
                        queue, emits, stats)
                else:
                    queue = insert(queue, emits)
            last_t = ts[jnp.maximum(length - 1, 0)]
            new_stats = {
                "batches": stats["batches"] + 1,
                "events": stats["events"] + length,
                "emitted": stats["emitted"]
                + jnp.sum(emits[:, 1] >= 0).astype(jnp.int32),
                "time": jnp.maximum(stats["time"], last_t),
            }
            self._count_window(stats, new_stats, tys, length)
            if spill:
                new_stats.update(spill_delta)
            elif fenced:
                # Fence-only carry: the bound is host-set between
                # segments and rides the loop unchanged.
                new_stats["bound_t"] = stats["bound_t"]
                new_stats["bound_seq"] = stats["bound_seq"]
            if validate_on:
                bits = self._cheap_fault_bits(queue)
                bits = bits | jnp.where(
                    (length > 0) & (ts[0] < prev_time),
                    jnp.int32(FAULT_CLOCK), jnp.int32(0),
                )
                new_stats["fault_word"] = stats["fault_word"] | bits
            return state, queue, new_stats

        return jax.lax.while_loop(cond, body, (state, queue, stats0))

    def run(self, state,
            queue: DeviceQueue | TieredDeviceQueue | Tiered3DeviceQueue,
            *, max_batches: int = 1 << 30, t_end: float | None = None,
            stats: Mapping | None = None):
        """Run to completion (or ``max_batches`` / horizon ``t_end``).

        ``t_end`` and ``max_batches`` override the engine defaults per
        call without recompiling (both are traced arguments): the
        extraction window is capped at t_end, so exactly the events
        with timestamp <= t_end execute and later ones stay queued.

        ``stats`` resumes a previous (segmented) run: pass the stats a
        prior ``run`` returned and the loop continues its cumulative
        counters — ``max_batches`` then caps the TOTAL batch count, not
        this call's increment.

        Stats carry ``type_counts`` (i32[T], events per type) and
        ``word_counts`` (i32[num_batches], batches per Horner word — the
        fused-dispatch profiling source) whenever the code space is
        small enough to track, plus the fault word /
        spill buffer when ``validate`` / ``overflow='spill'`` enable
        them.

        With ``validate != 'off'`` a set fault bit raises
        :class:`EngineFaultError` naming the first violated invariant
        and super-step; with ``overflow='error'`` the first dropped
        event does the same.
        """
        t_end = self.t_end if t_end is None else t_end
        if stats is None:
            stats0 = self.initial_run_stats()
        else:
            # "dropped" is surfaced on the way out (it lives on the
            # queue, not in the loop carry) — strip it on the way in.
            stats0 = {k: v for k, v in stats.items() if k != "dropped"}
        if self.validate != "off":
            # Entry audit: a queue corrupted BETWEEN segments (bad
            # restore, host-side mutation) would otherwise have its
            # poisoned front extracted on the first super-step, before
            # the in-loop bits (computed post-insert) ever see it.
            # Folding the incoming queue's bits into the carry makes
            # the loop guard trip before any event executes.
            # Jitted (and cached): eagerly the ~30 small ops dispatch
            # one by one at ~100x the cost of a single compiled call,
            # which would dominate the whole auditor's overhead.
            entry_fn = self.__dict__.get("_entry_bits_jit")
            if entry_fn is None:
                entry_fn = jax.jit(self._cheap_fault_bits)
                self._entry_bits_jit = entry_fn
            stats0 = dict(stats0)
            stats0["fault_word"] = stats0["fault_word"] | jnp.int32(
                entry_fn(queue))
        state, queue, stats = self._run_jit(
            state, queue, jnp.float32(t_end), jnp.int32(max_batches), stats0
        )
        stats = dict(stats)
        stats["dropped"] = queue.dropped
        if self.overflow == "error" and int(queue.dropped) > 0:
            raise EngineFaultError(
                FAULT_OVERFLOW, int(stats["batches"]),
                detail=(f"{int(queue.dropped)} event(s) overflowed the "
                        f"capacity-{self.capacity} queue"),
            )
        if self.validate != "off" and int(stats["fault_word"]) != 0:
            # The guard freezes the loop the moment the word sets, so
            # the word can only have been set by the LAST executed
            # super-step (batches - 1), or — when no super-step ran at
            # all — by the entry audit on the incoming queue (batches).
            final_b = int(stats["batches"])
            entry_b = int(stats0["batches"])
            raise EngineFaultError(
                int(stats["fault_word"]),
                final_b - 1 if final_b > entry_b else final_b,
            )
        if self.validate == "full":
            # Segment-boundary audit: each ``run`` call is one segment,
            # so the O(capacity) cross-tier sweep runs off the hot path.
            _validate.raise_on_findings(
                _validate.full_audit(
                    queue, local=(self.overflow == "spill")
                ),
                step=int(stats["batches"]),
            )
        return state, queue, stats

    def lower_run(self, state_spec, queue_spec):
        """AOT lowering hook (used by tests and the dry-run).

        Lowers the same jitted function as :meth:`run`, so the AOT
        executable keeps the documented queue-donation semantics.
        """
        t_spec = jax.ShapeDtypeStruct((), jnp.float32)
        mb_spec = jax.ShapeDtypeStruct((), jnp.int32)
        stats_spec = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype),
            self.initial_run_stats(),
        )
        return self._run_jit.lower(
            state_spec, queue_spec, t_spec, mb_spec, stats_spec
        )
