"""`SimProgram`: one declarative model definition, every runtime.

The paper's premise is that the modeler writes small event handlers once
and the *system* decides how to compose and execute them.  This module
is the API that delivers that split (DESIGN.md §1.1): a model is defined
exactly once on a :class:`SimProgram` —

    prog = SimProgram("mm1", config=Config(max_batch_len=4))

    @prog.handler("ARRIVE", lookahead=1.0, emits=True)
    def arrive(state, t, arg):
        ...
        return state, emits          # fixed-record delay rows, see below

    @prog.entity_handler("TALLY")    # vmap-able entity-parallel type
    def tally(entity_state, t, arg):
        ...
        return entity_state

    prog.schedule(0.0, "ARRIVE")

— and then compiled against any backend without touching the model:

    sim = prog.build(backend="device")               # tiered3 queue
    sim = prog.build(backend="device", shards=4)     # sharded, 4 queues
    sim = prog.build(backend="host", scheduler="speculative")
    result = sim.run(state0)         # -> RunResult, re-runnable

Portable emission convention
----------------------------
A handler registered with ``emits=True`` returns ``(state, emits)``
where ``emits`` is ``f32[config.max_emit, 2 + ARG_WIDTH]`` rows of
``(delay, type_id, arg...)``; rows with ``type_id < 0`` are ν-rows
(unused slots).  Delays are *relative to the handler's own timestamp*,
which is the one convention that can be compiled to both runtimes:

* device: a wrapper rewrites column 0 to the absolute time ``t + delay``
  (the on-device insert convention) inside the traced program;
* host: a wrapper returns the rows as ``(delay, type, arg)`` tuples and
  the host schedulers anchor them at the emitter's timestamp, skipping
  ν-rows after the batch returns concrete values.

Because both adapters wrap the SAME handler and both runtimes execute
events in the same ``(time, seq)`` order, a model built this way
produces bit-identical final states across every backend (the
executable contract lives in ``tests/test_simprogram_parity.py``).

Entity-parallel types (``entity_handler``) are written against an entity
slice of the state pytree (leading axis = entity, ``arg[0]`` = entity
index) and must not emit.  The sequential form every backend needs for
mixed windows is derived automatically; the device engine additionally
dispatches single-type runs of such events as one ``vmap`` over the
touched entities.
"""

from __future__ import annotations

import contextlib
import contextlib
import dataclasses
import functools
import warnings
from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.core import spans
from repro.core.events import ARG_WIDTH, EventRegistry
from repro.core.queue import HostEventQueue

EMIT_WIDTH = 2 + ARG_WIDTH

_HOST_SCHEDULERS = ("conservative", "speculative", "unbatched")
_QUEUE_MODES = ("tiered3", "tiered", "flat", "reference")
_DEFAULT_QUEUE_MODE = "tiered3"
_CHECK_MODES = ("off", "warn", "error")


class AnalysisError(ValueError):
    """``build(check="error")`` found error-severity static findings."""


@dataclasses.dataclass(frozen=True)
class Config:
    """Shared capacity/batch knobs — the part of the execution setup
    that must agree across backends for results to be comparable.

    ``capacity``/``max_emit`` only bound device-side buffers (the host
    heap is unbounded and host emission lists are sized by the same
    ``max_emit`` via the fixed-record convention).  ``codec`` selects
    the host batch-id codec; the device engine always uses the dense
    codec.
    """

    max_batch_len: int = 4
    capacity: int = 1024
    max_emit: int = 2
    codec: str = "dense"

    def __post_init__(self):
        if self.max_batch_len < 1:
            raise ValueError("max_batch_len must be >= 1")
        if self.capacity < 1:
            raise ValueError("capacity must be >= 1")
        if self.max_emit < 1:
            raise ValueError("max_emit must be >= 1")
        if self.codec not in ("dense", "paper"):
            raise ValueError(f"unknown codec {self.codec!r}")


@dataclasses.dataclass(frozen=True)
class _HandlerSpec:
    type_id: int
    name: str
    fn: Callable
    lookahead: float
    emits: bool
    entity: bool


def normalize_arg(arg, arg_width: int = ARG_WIDTH) -> np.ndarray:
    """Canonicalize an event argument to the fixed ``f32[ARG_WIDTH]``
    record every backend carries (None -> zeros; scalars/short vectors
    are zero-padded)."""
    if arg is None:
        return np.zeros((arg_width,), np.float32)
    a = np.asarray(arg, np.float32).reshape(-1)
    if a.size > arg_width:
        raise ValueError(
            f"event arg has {a.size} elements; ARG_WIDTH is {arg_width}"
        )
    out = np.zeros((arg_width,), np.float32)
    out[: a.size] = a
    return out


def _check_emits(emits, max_emit: int, name: str):
    emits = jnp.asarray(emits, jnp.float32)
    if emits.shape != (max_emit, EMIT_WIDTH):
        raise ValueError(
            f"handler {name!r} must return emits of shape "
            f"({max_emit}, {EMIT_WIDTH}) = (config.max_emit, 2+ARG_WIDTH) "
            f"rows of (delay, type, arg...); got {emits.shape}"
        )
    return emits


def _adapt_emits_host(fn: Callable, max_emit: int, name: str) -> Callable:
    """Portable delay rows -> host ``(delay, type, arg)`` tuples.

    The tuples keep traced values; the schedulers concretize them after
    the batch and skip ν-rows (type < 0)."""

    @functools.wraps(fn)
    def host_handler(state, t, arg):
        state, emits = fn(state, t, arg)
        emits = _check_emits(emits, max_emit, name)
        new = [(emits[i, 0], emits[i, 1], emits[i, 2:])
               for i in range(max_emit)]
        return state, new

    host_handler.returns_events = True
    return host_handler


def _adapt_emits_device(fn: Callable, max_emit: int, name: str) -> Callable:
    """Portable delay rows -> on-device absolute-time rows."""

    @functools.wraps(fn)
    def device_handler(state, t, arg):
        state, emits = fn(state, t, arg)
        emits = _check_emits(emits, max_emit, name)
        valid = emits[:, 1] >= 0
        times = jnp.where(valid, t + emits[:, 0], 0.0)
        return state, emits.at[:, 0].set(times)

    device_handler.returns_events = True
    return device_handler


def _sequential_from_entity(local: Callable, name: str) -> Callable:
    """Derive the whole-state sequential handler from an entity-local
    one: gather the entity row (``arg[0]``), apply, scatter back.

    This is the form mixed windows dispatch on every backend; the device
    engine's vmapped run path applies the same local handler per lane,
    so the two dispatch routes stay bit-identical.
    """

    @functools.wraps(local)
    def handler(state, t, arg):
        arg = jnp.asarray(arg, jnp.float32)
        eid = arg[0].astype(jnp.int32)
        sub = jax.tree.map(lambda leaf: leaf[eid], state)
        out = local(sub, t, arg)
        return jax.tree.map(
            lambda leaf, new: leaf.at[eid].set(new), state, out
        )

    handler.__name__ = f"entity_seq_{name}"
    return handler


@dataclasses.dataclass(frozen=True)
class RunResult:
    """Normalized result of one :meth:`CompiledSim.run`.

    ``events``/``batches``/``dropped``/``final_time`` mean the same
    thing on every backend (``dropped`` is always 0 on the host's
    unbounded heap; ``rollbacks`` is only nonzero under the speculative
    scheduler).  ``raw`` keeps the backend-native stats object.

    ``word_counts`` (device backends, when the code space is small
    enough to track) is the per-word batch histogram: entry ``c`` is
    the number of executed batches whose Horner composition code was
    ``c`` — the observable profiling source for
    ``build(..., dispatch_mode="fused", hot_words=...)`` hot-word
    selection (see :func:`repro.core.composer.hot_words_from_counts`);
    ``None`` on host backends.  ``type_counts`` (device backends, any
    code space) is the number of executed events of each type id.

    ``emitted``/``pending``/``spilled`` (device backends) complete the
    conservation law ``seeded + ingested + emitted == events + pending
    + dropped + spilled + shed``; ``fault_word``/``fault_step`` surface
    the on-device auditor's packed invariant bits (``0``/``-1`` when
    clean or when ``validate="off"``) — see :mod:`repro.core.validate`.

    ``ingested``/``shed`` account the open-system arrival stream of
    ``run(arrivals=...)`` (DESIGN.md §10): ``ingested`` counts every
    arrival CONSUMED from the source — absorbed into the queue, parked
    in the spill pool, or refused — mirroring how ``emitted`` counts
    dropped/spilled emits; ``shed`` is the refused subset (nonzero only
    under ``backpressure="shed"``), which balances the law's right side
    exactly like ``dropped`` does for emits.  Both are 0 for closed
    runs on every backend.

    ``boundaries`` (device backends) counts the segment boundaries the
    driver ran: one per ``engine.run`` call, each followed by one
    compiled read of the queue and the stats
    (:meth:`~repro.core.engine.DeviceEngine.boundary_read`).  0 on host
    backends.
    """

    state: Any
    events: int
    batches: int
    dropped: int
    final_time: float
    rollbacks: int = 0
    raw: Any = None
    word_counts: Any = None
    type_counts: Any = None
    emitted: int = 0
    pending: int = 0
    spilled: int = 0
    fault_word: int = 0
    fault_step: int = -1
    ingested: int = 0
    shed: int = 0
    boundaries: int = 0

    @property
    def mean_batch_length(self) -> float:
        return self.events / self.batches if self.batches else 0.0

    def stats(self) -> dict:
        return {
            "events": self.events,
            "batches": self.batches,
            "dropped": self.dropped,
            "final_time": self.final_time,
            "rollbacks": self.rollbacks,
            "mean_batch_length": self.mean_batch_length,
            "emitted": self.emitted,
            "pending": self.pending,
            "spilled": self.spilled,
            "fault_word": self.fault_word,
            "fault_step": self.fault_step,
            "ingested": self.ingested,
            "shed": self.shed,
        }


class SimProgram:
    """Declarative model: event alphabet + lookaheads + initial events.

    Registration (``handler`` / ``entity_handler`` / ``register``) must
    happen before the program is frozen; :meth:`build` freezes it.
    Initial events may be scheduled at any time — they are snapshotted
    into each :class:`CompiledSim` run, never consumed.
    """

    def __init__(self, name: str = "sim", config: Config | None = None):
        self.name = name
        self.config = config or Config()
        self._specs: list[_HandlerSpec] = []
        self._by_name: dict[str, _HandlerSpec] = {}
        self._schedule: list[tuple[float, int, np.ndarray]] = []
        self._frozen = False
        self._registries: dict[str, EventRegistry] = {}
        self._example_state = None
        self._entries: set[str] = set()

    # -- registration -----------------------------------------------------
    def register(self, name: str, fn: Callable, *,
                 lookahead: float = float("inf"), emits: bool = False,
                 entity: bool = False) -> _HandlerSpec:
        """Register one event type.  ``emits=True`` handlers follow the
        portable fixed-record delay convention (module docstring);
        ``entity=True`` handlers are entity-local and must not emit."""
        if self._frozen:
            raise RuntimeError(
                "SimProgram is frozen; register all event types before "
                "build() (paper §III-A: constant handler array)"
            )
        if name in self._by_name:
            raise ValueError(f"event type {name!r} already registered")
        if entity and emits:
            raise ValueError(
                f"entity-parallel type {name!r} must not emit events "
                "(vmapped run dispatch has no emission lanes)"
            )
        spec = _HandlerSpec(
            type_id=len(self._specs), name=name, fn=fn,
            lookahead=float(lookahead), emits=bool(emits),
            entity=bool(entity),
        )
        self._specs.append(spec)
        self._by_name[name] = spec
        return spec

    def handler(self, name: str | Callable | None = None, *,
                lookahead: float = float("inf"), emits: bool = False):
        """Decorator form: ``@prog.handler("ARRIVE", lookahead=1.0,
        emits=True)`` (or bare ``@prog.handler``)."""
        if callable(name):
            fn, name = name, None
            self.register(fn.__name__, fn)
            return fn

        def wrap(fn):
            self.register(name or fn.__name__, fn,
                          lookahead=lookahead, emits=emits)
            return fn

        return wrap

    def entity_handler(self, name: str | Callable | None = None, *,
                       lookahead: float = float("inf")):
        """Decorator registering an entity-parallel type.  The function
        maps an entity slice: ``(entity_state, t, arg) -> entity_state``
        with ``arg[0]`` the entity index and every state leaf carrying
        the entity dimension on axis 0."""
        if callable(name):
            fn, name = name, None
            self.register(fn.__name__, fn, entity=True)
            return fn

        def wrap(fn):
            self.register(name or fn.__name__, fn,
                          lookahead=lookahead, entity=True)
            return fn

        return wrap

    # -- initial events ---------------------------------------------------
    def schedule(self, time: float, name: str, arg: Any = None) -> None:
        """Add one initial event (by type name; ``arg`` is canonicalized
        to the fixed f32[ARG_WIDTH] record)."""
        if name not in self._by_name:
            raise KeyError(
                f"unknown event type {name!r}; registered: "
                f"{sorted(self._by_name)}"
            )
        self._schedule.append(
            (float(time), self._by_name[name].type_id, normalize_arg(arg))
        )

    def schedule_many(
        self, events: Iterable[tuple[float, str] | tuple[float, str, Any]]
    ) -> None:
        for ev in events:
            self.schedule(*ev)

    def scheduled_events(self) -> list[tuple[float, int, np.ndarray]]:
        """Snapshot of the initial events as (time, type_id, arg_vec)."""
        return list(self._schedule)

    # -- static analysis ---------------------------------------------------
    def example_state(self, state) -> "SimProgram":
        """Declare a representative initial state (shapes/dtypes only;
        values are never read).  This is what the static analyzer
        traces handlers against, what ``build(check=...)`` analyzes at
        build time, and what ``hot_words="static"`` needs.  Allowed
        after freeze — it is metadata, not a handler."""
        self._example_state = state
        return self

    def external_entry(self, *names: str) -> "SimProgram":
        """Declare event types injected from OUTSIDE the program — e.g.
        an open-system arrival stream (``run(arrivals=...)``) or ad-hoc
        ``run(events=...)`` seeds.  Reachability treats them as roots
        alongside the schedule, so they (and what they reach) are not
        reported as dead."""
        for name in names:
            if name not in self._by_name:
                raise KeyError(
                    f"unknown event type {name!r}; registered: "
                    f"{sorted(self._by_name)}"
                )
            self._entries.add(name)
        return self

    def analyze(self, state=None, roots=()):
        """Run the static analyzer (:mod:`repro.analysis`) over this
        program; returns a ``ProgramReport``.  ``state`` defaults to
        the declared :meth:`example_state`."""
        from repro.analysis import analyze as _analyze

        return _analyze(self, state=state, roots=roots)

    def _run_check(self, mode: str, state=None):
        """Shared ``check=`` implementation: analyze, then raise
        (``"error"``) or warn (``"warn"``) on error-severity findings."""
        report = self.analyze(state=state)
        if report.errors:
            msg = (
                f"static analysis found {len(report.errors)} "
                f"error-severity finding(s) in {self.name!r}:\n  "
                + "\n  ".join(str(f) for f in report.errors)
            )
            if mode == "error":
                raise AnalysisError(msg)
            warnings.warn(msg, stacklevel=3)
        return report

    # -- introspection ----------------------------------------------------
    def freeze(self) -> "SimProgram":
        self._frozen = True
        return self

    @property
    def frozen(self) -> bool:
        return self._frozen

    @property
    def names(self) -> list[str]:
        return [s.name for s in self._specs]

    def type_id(self, name: str) -> int:
        return self._by_name[name].type_id

    def __len__(self) -> int:
        return len(self._specs)

    # -- backend registries ------------------------------------------------
    def _registry(self, backend: str) -> EventRegistry:
        self.freeze()
        if backend not in self._registries:
            adapt = (_adapt_emits_device if backend == "device"
                     else _adapt_emits_host)
            reg = EventRegistry()
            for spec in self._specs:
                fn = spec.fn
                if spec.entity:
                    fn = _sequential_from_entity(fn, spec.name)
                if spec.emits:
                    fn = adapt(fn, self.config.max_emit, spec.name)
                reg.register(spec.name, fn, lookahead=spec.lookahead)
            self._registries[backend] = reg.freeze()
        return self._registries[backend]

    def host_registry(self) -> EventRegistry:
        """Registry with handlers adapted to the host schedulers'
        list-of-``(delay, type, arg)`` emission convention."""
        return self._registry("host")

    def device_registry(self) -> EventRegistry:
        """Registry with handlers adapted to the on-device absolute-time
        fixed-record emission convention."""
        return self._registry("device")

    def device_entity_handlers(self) -> dict[int, Callable]:
        """type_id -> entity-local handler, for the device engine's
        vmapped single-type-run dispatch."""
        return {s.type_id: s.fn for s in self._specs if s.entity}

    # -- compilation -------------------------------------------------------
    def build(self, *, backend: str = "device",
              scheduler: str = "conservative", composer: str = "lazy",
              queue_mode: str = _DEFAULT_QUEUE_MODE,
              shards: int | None = None, shard_fn=None,
              placement: str = "serial",
              capacity: int | None = None,
              front_cap: int | None = None, stage_cap: int | None = None,
              num_runs: int | None = None,
              dispatch_mode: str | None = None,
              hot_words: Sequence | str | None = None,
              queue_kernels: str = "xla",
              validate: str = "off",
              overflow: str = "drop",
              check: str = "off",
              state_spec=None, arg_spec=None,
              check_causality: bool = False,
              window_slack: float = float("inf"),
              jit_handlers: bool = True) -> "CompiledSim":
        """Compile this model against one runtime.

        ``backend="device"`` honors ``queue_mode`` (default
        ``"tiered3"`` — bounded per-batch cost at any capacity,
        DESIGN.md §4.4) plus the optional capacity/tier overrides, and
        ``shards=N`` (with optional ``shard_fn``): N per-shard tiered3
        queues run under the lookahead-synchronized
        :class:`~repro.core.sharded.ShardedDeviceEngine`,
        bit-identical to the single queue (DESIGN.md §5.1) —
        entity-parallel types route by their entity index
        (``arg[0]``) by default.  ``placement`` picks how the shards
        execute: ``"serial"`` (default) unrolls the per-shard legs in
        one program, ``"devices"`` maps the stacked shard queues over
        a 1-D device mesh under ``shard_map`` — one device per shard,
        bit-identical to serial (DESIGN.md §12; needs ``shards``
        visible devices, e.g.
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).  ``dispatch_mode`` selects the window
        dispatch path (``"switch"``: one switch over every composed
        word; ``"masked"``: the generic per-lane path; ``"fused"``:
        top-W hot-word super-procedures + masked fallback, DESIGN.md
        §7) — all three bit-identical; left ``None``, the word count
        picks ``"switch"`` up to 4,096 words and ``"masked"`` above; ``hot_words`` declares the
        fused hot set as sequences of type names or ids (default: the
        first 32 dense codes; profile a run's
        ``RunResult.word_counts`` for a real selection), or the string
        ``"static"`` to take the top reachable compositions from the
        static analyzer (DESIGN.md §11; needs
        :meth:`example_state`).
        ``queue_kernels="pallas"`` swaps the tiered3 front-tier hot
        loops for the Pallas kernels (interpret mode on CPU).
        ``validate`` arms the on-device invariant auditor (DESIGN.md
        §9): ``"cheap"`` folds per-super-step fault bits into the
        loop carry (CI-gated at <=1.10x the ``"off"`` cost),
        ``"full"`` adds an exact audit at segment boundaries; a
        violation raises :class:`~repro.core.validate.EngineFaultError`
        naming the invariant and super-step.  ``overflow`` picks the
        full-queue policy: ``"drop"`` (count ghosts), ``"error"``
        (fail fast), or ``"spill"`` (divert to a host pool reabsorbed
        at segment boundaries — bit-parity with an oversized queue).
        ``backend="host"`` honors
        ``scheduler`` and ``composer`` (+ eager specs / causality /
        slack knobs).  Passing a knob that the selected backend does
        not read is an error, not a silent default — a mis-targeted
        ``scheduler=`` must not quietly run a different runtime.
        ``check`` (backend-neutral) runs the static analyzer
        (DESIGN.md §11) over the model: ``"error"`` raises
        :class:`AnalysisError` on any error-severity finding (unsound
        lookahead, malformed emit rows, impure handlers) before a
        single event executes; ``"warn"`` reports them as warnings.
        The analysis runs at build time when :meth:`example_state` is
        declared, otherwise it is deferred to the first
        :meth:`CompiledSim.run`, which checks against the run's own
        initial state before dispatching anything.
        Everything model-level — handlers, lookaheads, Config, initial
        events — comes from the program; nothing about the model is
        repeated at the call site.  ``max_emit`` is Config-only: the
        portable emit-row shape is baked into the handler adapters.
        """
        self.freeze()
        if check not in _CHECK_MODES:
            raise ValueError(
                f"unknown check mode {check!r}; expected one of "
                f"{_CHECK_MODES}"
            )
        deferred_check = "off"
        if check != "off":
            if self._example_state is not None:
                self._run_check(check)
            else:
                deferred_check = check
        if backend == "device":
            from repro.core.engine import DeviceEngine
            from repro.core.sharded import ShardedDeviceEngine

            misdirected = {
                "scheduler": scheduler != "conservative",
                "composer": composer != "lazy",
                "state_spec": state_spec is not None,
                "arg_spec": arg_spec is not None,
                "check_causality": check_causality,
                "window_slack": window_slack != float("inf"),
                "jit_handlers": not jit_handlers,
            }
            bad = [k for k, hit in misdirected.items() if hit]
            if bad:
                raise ValueError(
                    f"{bad} are host-backend knobs; the device backend "
                    "would silently ignore them — drop them or build "
                    "with backend='host'"
                )
            if queue_mode not in _QUEUE_MODES:
                raise ValueError(
                    f"unknown queue_mode {queue_mode!r}; "
                    f"expected one of {_QUEUE_MODES}"
                )
            if shard_fn is not None and shards is None:
                raise ValueError("shard_fn requires shards=N")
            if placement != "serial" and shards is None:
                raise ValueError(
                    f"placement={placement!r} requires shards=N (it "
                    "places the sharded engine's per-shard queues)"
                )
            if isinstance(hot_words, str):
                if hot_words != "static":
                    raise ValueError(
                        f"unknown hot_words spec {hot_words!r}; "
                        "expected 'static' or a sequence of words"
                    )
                if self._example_state is None:
                    raise ValueError(
                        "hot_words='static' derives the hot set from "
                        "the static analyzer, which needs a state "
                        "template — declare one with "
                        "prog.example_state(state) first"
                    )
                hot_words = self.analyze().static_hot_words()
            if hot_words is not None:
                # Type names are the API-level spelling; the engines
                # take ids.
                hot_words = [
                    tuple(self.type_id(t) if isinstance(t, str) else int(t)
                          for t in word)
                    for word in hot_words
                ]
            if shards is not None:
                if queue_mode != "tiered3":
                    raise ValueError(
                        f"shards={shards} requires queue_mode='tiered3' "
                        f"(got {queue_mode!r}): the per-shard pending "
                        "sets are tiered3 queues"
                    )
                engine = ShardedDeviceEngine.from_program(
                    self, shards=shards, shard_fn=shard_fn,
                    placement=placement,
                    capacity=capacity, front_cap=front_cap,
                    stage_cap=stage_cap, num_runs=num_runs,
                    dispatch_mode=dispatch_mode, hot_words=hot_words,
                    queue_kernels=queue_kernels,
                    validate=validate, overflow=overflow,
                )
                variant = f"tiered3/shards={shards}"
                if placement != "serial":
                    variant += f"/{placement}"
                return CompiledSim(
                    self, backend="device", engine=engine,
                    variant=variant, check=deferred_check,
                )
            engine = DeviceEngine.from_program(
                self, queue_mode=queue_mode, capacity=capacity,
                front_cap=front_cap, stage_cap=stage_cap,
                num_runs=num_runs,
                dispatch_mode=dispatch_mode, hot_words=hot_words,
                queue_kernels=queue_kernels,
                validate=validate, overflow=overflow,
            )
            return CompiledSim(self, backend="device", engine=engine,
                               variant=queue_mode, check=deferred_check)
        if backend == "host":
            misdirected = {
                "queue_mode": queue_mode != _DEFAULT_QUEUE_MODE,
                "shards": shards is not None,
                "shard_fn": shard_fn is not None,
                "placement": placement != "serial",
                "capacity": capacity is not None,
                "front_cap": front_cap is not None,
                "stage_cap": stage_cap is not None,
                "num_runs": num_runs is not None,
                "dispatch_mode": dispatch_mode is not None,
                "hot_words": hot_words is not None,
                "queue_kernels": queue_kernels != "xla",
                "validate": validate != "off",
                "overflow": overflow != "drop",
            }
            bad = [k for k, hit in misdirected.items() if hit]
            if bad:
                raise ValueError(
                    f"{bad} are device-backend knobs; the host backend "
                    "would silently ignore them — drop them or build "
                    "with backend='device'"
                )
            from repro.core.composer import EagerComposer, LazyComposer
            from repro.core.scheduler import (
                ConservativeScheduler,
                SpeculativeScheduler,
            )

            if scheduler not in _HOST_SCHEDULERS:
                raise ValueError(
                    f"unknown scheduler {scheduler!r}; "
                    f"expected one of {_HOST_SCHEDULERS}"
                )
            if scheduler == "unbatched":
                return CompiledSim(self, backend="host", variant="unbatched",
                                   jit_handlers=jit_handlers,
                                   check=deferred_check)
            if composer == "lazy":
                comp = LazyComposer.from_program(self)
            elif composer == "eager":
                if arg_spec is None:
                    arg_spec = jax.ShapeDtypeStruct(
                        (ARG_WIDTH,), jnp.float32
                    )
                comp = EagerComposer.from_program(
                    self, state_spec=state_spec, arg_spec=arg_spec
                )
            else:
                raise ValueError(f"unknown composer {composer!r}")
            if scheduler == "conservative":
                sched = ConservativeScheduler.from_program(
                    self, composer=comp, check_causality=check_causality
                )
            else:
                sched = SpeculativeScheduler.from_program(
                    self, composer=comp, window_slack=window_slack
                )
            return CompiledSim(self, backend="host", sched=sched,
                               variant=scheduler, check=deferred_check)
        raise ValueError(
            f"unknown backend {backend!r}; expected 'device' or 'host'"
        )


class CompiledSim:
    """One (model, runtime) pairing with a uniform ``run`` contract.

    ``run`` is re-runnable: every call rebuilds the initial pending set
    from the program's schedule.  On the device backend that hides the
    queue-donation footgun — the donated (consumed) queue value is an
    internal detail, callers never hold one.  Composed batch programs
    and the engine's jitted main loop are cached on this object, so
    repeat runs pay no recompilation.
    """

    def __init__(self, program: SimProgram, *, backend: str,
                 engine=None, sched=None, variant: str = "",
                 jit_handlers: bool = True, check: str = "off"):
        self.program = program
        self.backend = backend
        self.engine = engine
        self.sched = sched
        self.variant = variant
        self.jit_handlers = jit_handlers
        # Deferred build(check=...): no example state was declared, so
        # the analyzer runs against the first run()'s own initial
        # state — still before any event executes — then caches.
        self.check = check
        self._check_done = False

    def __repr__(self):
        return (f"CompiledSim({self.program.name!r}, "
                f"backend={self.backend!r}, variant={self.variant!r})")

    @property
    def registry(self) -> EventRegistry:
        return (self.program.device_registry() if self.backend == "device"
                else self.program.host_registry())

    def _initial_events(self, events):
        if events is None:
            evs = self.program.scheduled_events()
        else:
            evs = []
            for (t, ty, *rest) in events:
                type_id = (self.program.type_id(ty) if isinstance(ty, str)
                           else int(ty))
                arg = rest[0] if rest else None
                evs.append((float(t), type_id, normalize_arg(arg)))
        return evs

    # -- segmented device driver -------------------------------------------
    def _rebalance_spill(self, queue, pool_rows, pool_seqs):
        """The pool outgrew the queue's slack: merge queue ∪ pool and
        keep the lex-smallest ``capacity`` events on device; the rest
        stays host-side.  Host O(capacity log capacity) at a segment
        boundary (off the hot path); the global counters are preserved
        exactly, so the logical pending set is untouched — only its
        device/host split moves.
        """
        from repro.core.queue import (
            tiered3_queue_from_host,
            tiered3_queue_to_flat,
        )

        eng = self.engine
        flat = tiered3_queue_to_flat(queue)
        occ = np.asarray(flat.types) >= 0
        times = np.concatenate(
            [np.asarray(flat.times)[occ], pool_rows[:, 0]]
        )
        types = np.concatenate(
            [np.asarray(flat.types)[occ],
             pool_rows[:, 1].astype(np.int32)]
        )
        args = np.concatenate(
            [np.asarray(flat.args)[occ], pool_rows[:, 2:]]
        )
        seqs = np.concatenate([np.asarray(flat.seqs)[occ], pool_seqs])
        order = np.lexsort((seqs, times))
        C = eng.capacity
        keep, rest = order[:C], order[C:]
        q = tiered3_queue_from_host(
            [(float(times[i]), int(types[i]), args[i]) for i in keep],
            C, front_cap=eng.front_cap, stage_cap=eng.stage_cap,
            num_runs=eng.num_runs, seqs=seqs[keep],
        )
        q = q._replace(next_seq=queue.next_seq, dropped=queue.dropped)
        new_rows = np.zeros((rest.size, EMIT_WIDTH), np.float32)
        new_rows[:, 0] = times[rest]
        new_rows[:, 1] = types[rest]
        new_rows[:, 2:] = args[rest]
        return q, new_rows, seqs[rest].astype(np.int32)

    def _absorb_spill(self, queue, pool_rows, pool_seqs, stats, occ):
        """Reabsorb the host spill pool — wholesale when it fits,
        otherwise via the lex rebalance — and refresh the engine's
        execution fence to the lex-earliest key still outstanding.
        ``occ`` is the queue's occupancy from the boundary read.
        Returns ``(queue, pool_rows, pool_seqs, stats)``."""
        from repro.core.queue import tiered3_queue_absorb_rows

        eng = self.engine
        if pool_seqs.size:
            room = eng.capacity - occ
            if room >= int(pool_seqs.size):
                queue = tiered3_queue_absorb_rows(
                    queue, jnp.asarray(pool_rows),
                    jnp.asarray(pool_seqs),
                )
                pool_rows = np.zeros((0, EMIT_WIDTH), np.float32)
                pool_seqs = np.zeros((0,), np.int32)
            else:
                queue, pool_rows, pool_seqs = self._rebalance_spill(
                    queue, pool_rows, pool_seqs
                )
        stats = dict(eng.initial_run_stats() if stats is None else stats)
        if pool_seqs.size:
            order = np.lexsort((pool_seqs, pool_rows[:, 0]))
            stats["bound_t"] = jnp.float32(pool_rows[order[0], 0])
            stats["bound_seq"] = jnp.int32(pool_seqs[order[0]])
        else:
            stats["bound_t"] = jnp.float32(np.inf)
            stats["bound_seq"] = jnp.int32(2**31 - 1)
        return queue, pool_rows, pool_seqs, stats

    def _absorb_fn(self):
        """Jitted masked arrival absorb, cached per CompiledSim.

        The admitted count rides a traced ``[lo, hi)`` prefix mask and
        the queue is donated, so ONE compile serves every segment
        boundary of a streamed run — the per-boundary cost is a device
        call, not a trace."""
        fn = getattr(self, "_absorb_jit", None)
        if fn is None:
            eng = self.engine

            def absorb(queue, rows, seqs, lo, hi):
                idx = jnp.arange(rows.shape[0], dtype=jnp.int32)
                return eng.absorb_rows(
                    queue, rows, seqs, (idx >= lo) & (idx < hi)
                )

            fn = jax.jit(absorb, donate_argnums=(0,))
            self._absorb_jit = fn
        return fn

    @staticmethod
    def _save_checkpoint(manager, step, state, queue, stats,
                         pool_rows, pool_seqs, *, extra=None, strip=()):
        # "dropped" lives on the queue (re-derived after every segment),
        # not in the loop carry — keep the saved stats restorable
        # against the initial_run_stats template.  Fence-only streamed
        # runs additionally strip the host-injected bound keys (the
        # template never carries them; they are recomputed from the
        # restored cursor at the first resumed boundary).
        drop = {"dropped", *strip}
        payload = {
            "state": state,
            "queue": queue,
            "stats": {k: v for k, v in stats.items() if k not in drop},
            "pool_rows": np.asarray(pool_rows),
            "pool_seqs": np.asarray(pool_seqs),
        }
        if extra:
            payload.update(extra)
        manager.save_async(step, payload)

    def _run_device(self, state, evs, t_end, total_batches, *,
                    checkpoint_every, checkpoint_dir, resume_from,
                    segment_hook, arrivals=None, backpressure="block",
                    stream_prefetch=True):
        eng = self.engine
        spill = getattr(eng, "overflow", "drop") == "spill"
        streamed = arrivals is not None
        if streamed:
            if getattr(eng, "queue_mode", None) != "tiered3":
                raise ValueError(
                    "run(arrivals=...) on the device backend requires "
                    f"queue_mode='tiered3', got {eng.queue_mode!r}: the "
                    "admission fence is a tiered3 lex bound"
                )
            from repro.core.sharded import ShardedDeviceEngine
            if (eng.queue_kernels == "pallas"
                    and not isinstance(eng, ShardedDeviceEngine)):
                raise ValueError(
                    "run(arrivals=...) needs the bounded extract's lex "
                    "fence, which the pallas front tier does not "
                    "implement — build with queue_kernels='xla'"
                )
        if (checkpoint_every is not None or resume_from is not None) \
                and checkpoint_dir is None:
            raise ValueError(
                "checkpoint_every/resume_from require checkpoint_dir="
            )
        seg = None if checkpoint_every is None else int(checkpoint_every)
        if seg is not None and seg < 1:
            raise ValueError(f"checkpoint_every must be >= 1, got {seg}")
        manager = None
        if checkpoint_dir is not None:
            from repro.checkpoint.manager import CheckpointManager
            manager = CheckpointManager(checkpoint_dir)

        if spill:
            queue, pool_rows, pool_seqs = eng.initial_queue_spill(evs)
        else:
            queue = eng.initial_queue(evs)
            pool_rows = np.zeros((0, EMIT_WIDTH), np.float32)
            pool_seqs = np.zeros((0,), np.int32)
        stats = None
        cursor, ingested, shed = 0, 0, 0
        if streamed:
            # Reserve the arrival seq range upfront: arrival j carries
            # seq len(evs)+j, and mid-run emits draw seqs PAST the
            # reservation — so an absorbed arrival occupies exactly the
            # (time, seq) lex rank it would have had pre-seeded, even
            # under timestamp ties (DESIGN.md §10).
            queue = queue._replace(
                next_seq=queue.next_seq + jnp.int32(len(arrivals))
            )

        if resume_from is not None:
            step = None if resume_from == "latest" else int(resume_from)
            restored, at_step = manager.restore({
                "state": state,
                "queue": queue,
                "stats": eng.initial_run_stats(),
            }, step)
            state, queue = restored["state"], restored["queue"]
            # Restored leaves land on the default device; a placed
            # engine (sharded placement="devices") re-shards them.
            queue = eng.place_queue(queue)
            stats = restored["stats"]
            pool_rows = np.asarray(
                manager.restore_leaf("pool_rows", at_step), np.float32
            )
            pool_seqs = np.asarray(
                manager.restore_leaf("pool_seqs", at_step), np.int32
            )
            saved_cursor = manager.restore_leaf(
                "ingest_cursor", at_step, default=None
            )
            if saved_cursor is not None and not streamed:
                raise ValueError(
                    "checkpoint was written by a streamed run "
                    f"(arrival cursor {int(saved_cursor)}): resume with "
                    "the same arrivals= source"
                )
            if streamed and saved_cursor is not None:
                cursor = int(np.asarray(saved_cursor))
                ingested = int(np.asarray(manager.restore_leaf(
                    "ingested", at_step, default=np.int64(0))))
                shed = int(np.asarray(manager.restore_leaf(
                    "shed", at_step, default=np.int64(0))))

        feeder = None
        if streamed:
            from repro.stream.ingest import StreamFeeder
            feeder = StreamFeeder(
                arrivals, len(evs), start=cursor,
                prefetch=stream_prefetch,
            )

        seg_index = 0
        idle_rounds = 0
        try:
            (state, queue, stats, pool_rows, pool_seqs,
             ingested, shed, seg_index, read) = self._segment_loop(
                state, queue, stats, pool_rows, pool_seqs,
                t_end=t_end, total_batches=total_batches, seg=seg,
                spill=spill, manager=manager, segment_hook=segment_hook,
                seg_index=seg_index, idle_rounds=idle_rounds,
                feeder=feeder, backpressure=backpressure,
                ingested=ingested, shed=shed,
            )
        finally:
            if feeder is not None:
                feeder.close()
            if manager is not None:
                # Even on a fault path, drain the async writer so the
                # newest on-disk checkpoint is complete (atomic rename
                # means a partial write is never visible as "latest").
                manager.wait()

        word_counts = stats.get("word_counts")
        type_counts = stats.get("type_counts")
        raw = dict(stats)
        raw["final_queue"] = queue
        return RunResult(
            state=state,
            events=int(stats["events"]),
            batches=int(stats["batches"]),
            dropped=int(stats["dropped"]),
            final_time=float(stats["time"]),
            raw=raw,
            word_counts=(None if word_counts is None
                         else np.asarray(word_counts)),
            type_counts=(None if type_counts is None
                         else np.asarray(type_counts)),
            emitted=int(np.asarray(stats.get("emitted", 0))),
            pending=read.occupancy,
            spilled=int(pool_seqs.size),
            fault_word=int(np.asarray(stats.get("fault_word", 0))),
            fault_step=int(np.asarray(stats.get("fault_step", -1))),
            ingested=int(ingested),
            shed=int(shed),
            boundaries=seg_index,
        )

    def _segment_loop(self, state, queue, stats, pool_rows, pool_seqs, *,
                      t_end, total_batches, seg, spill, manager,
                      segment_hook, seg_index, idle_rounds,
                      feeder=None, backpressure="block",
                      ingested=0, shed=0):
        from repro.core.validate import (
            FAULT_INGEST,
            FAULT_SPILL_STALL,
            EngineFaultError,
        )

        eng = self.engine
        streamed = feeder is not None
        span = jax.profiler.TraceAnnotation
        # One ``des.boundary`` span per boundary: it opens after each
        # segment (and before the first) and closes as the next starts.
        # ``read`` is the engine's one compiled read of the queue and
        # the stats (batches, spill count, occupancy, next time); it is
        # taken again whenever the queue changes outside a segment.
        with contextlib.ExitStack() as boundary:
            boundary.enter_context(span(spans.BOUNDARY))
            read = eng.boundary_read(queue, stats)
            while True:
                progressed = False
                if spill and pool_seqs.size:
                    with span(spans.SPILL):
                        queue, pool_rows, pool_seqs, stats = \
                            self._absorb_spill(
                                queue, pool_rows, pool_seqs, stats,
                                read.occupancy)
                        read = eng.boundary_read(queue, stats)
                # -- streamed admission: at most ONE arrival block per
                # boundary, so the admitted/spilled/shed split is a pure
                # function of the cursor, the horizon, and queue occupancy
                # — never of prefetch timing.
                if streamed and feeder.has_pending():
                    # Arrivals past the horizon are never consumed: they
                    # stay in the source, like queued events past t_end
                    # stay in the queue.
                    adm = feeder.admissible(t_end)
                    if adm:
                        with span(spans.OCCUPANCY):
                            occ = read.occupancy
                        k = min(adm, max(eng.capacity - occ, 0))
                        if k > 0:
                            with span(spans.ABSORB, rows=k):
                                rows_d, seqs_d, lo = feeder.device_block()
                                queue = self._absorb_fn()(
                                    queue, rows_d, seqs_d,
                                    jnp.int32(lo), jnp.int32(lo + k),
                                )
                            feeder.advance(k)
                            ingested += k
                            progressed = True
                        rest = adm - k
                        if rest > 0:
                            if spill:
                                r_rows, r_seqs = feeder.host_slice(rest)
                                pool_rows = np.concatenate(
                                    [pool_rows, r_rows])
                                pool_seqs = np.concatenate(
                                    [pool_seqs, r_seqs])
                                feeder.advance(rest)
                                ingested += rest
                                progressed = True
                            elif backpressure == "shed":
                                feeder.advance(rest)
                                ingested += rest
                                shed += rest
                                progressed = True
                            elif backpressure == "error":
                                raise EngineFaultError(
                                    FAULT_INGEST, read.batches,
                                    detail=(
                                        f"{rest} arrival(s) found the "
                                        f"capacity-{eng.capacity} queue "
                                        "full (backpressure='error')"
                                    ),
                                )
                            # backpressure='block': the rows wait in the
                            # feeder; the fence keeps order safe and the
                            # stall detector below converts a wedged
                            # topology into FAULT_INGEST.
                if streamed:
                    # Refresh the admission fence: the lex-min outstanding
                    # external key — next unconsumed arrival vs. spilled
                    # pool head — with (inf, I32_MAX) meaning no fence.
                    # Reading the next key may wait for the feeder's
                    # next block.
                    with span(spans.FENCE):
                        stats = dict(eng.initial_run_stats()
                                     if stats is None else stats)
                        f_t, f_s = feeder.next_key()
                        if spill and pool_seqs.size:
                            order = np.lexsort(
                                (pool_seqs, pool_rows[:, 0]))
                            p_key = (float(pool_rows[order[0], 0]),
                                     int(pool_seqs[order[0]]))
                            if p_key < (f_t, f_s):
                                f_t, f_s = p_key
                        stats["bound_t"] = jnp.float32(f_t)
                        stats["bound_seq"] = jnp.int32(f_s)
                done = read.batches
                target = (total_batches if seg is None
                          else min(total_batches, done + seg))
                boundary.close()
                with span(spans.SEGMENT):
                    state, queue, stats = eng.run(
                        state, queue, max_batches=target, t_end=t_end,
                        stats=stats,
                    )
                    read = eng.boundary_read(queue, stats)
                    new_done = read.batches
                boundary.enter_context(span(spans.BOUNDARY))
                if new_done > done:
                    progressed = True
                if spill:
                    with span(spans.SPILL):
                        n = read.spill_n
                        if n > 0:
                            pool_rows = np.concatenate([
                                pool_rows,
                                np.asarray(stats["spill_rows"])[:n]])
                            pool_seqs = np.concatenate([
                                pool_seqs,
                                np.asarray(stats["spill_seqs"])[:n]])
                            stats = dict(stats)
                            stats["spill_n"] = jnp.int32(0)
                seg_index += 1
                # Save BEFORE the injection seam: the newest checkpoint is
                # always a clean pre-corruption snapshot, so fault recovery
                # is restore-latest-and-replay.
                if manager is not None and seg is not None:
                    with span(spans.CHECKPOINT):
                        self._save_checkpoint(
                            manager, new_done, state, queue, stats,
                            pool_rows, pool_seqs,
                            extra=(dict(
                                ingest_cursor=np.int64(feeder.cursor),
                                ingested=np.int64(ingested),
                                shed=np.int64(shed),
                            ) if streamed else None),
                            strip=(("bound_t", "bound_seq")
                                   if streamed and not spill else ()),
                        )
                if segment_hook is not None:
                    out = segment_hook(seg_index, state, queue, stats)
                    if out is not None:
                        state, queue, stats = out
                        read = eng.boundary_read(queue, stats)
                if new_done >= total_batches:
                    break
                pool_live = bool(spill and pool_seqs.size)
                feeder_live = streamed and feeder.has_pending()
                if pool_live or feeder_live:
                    with span(spans.NEXT_TIME):
                        qt = read.next_time
                        pool_t = (float(pool_rows[:, 0].min()) if pool_live
                                  else float("inf"))
                        feed_t = (feeder.next_time() if feeder_live
                                  else float("inf"))
                    if qt > t_end and pool_t > t_end and feed_t > t_end:
                        # Everything outstanding is past the horizon — the
                        # external remainder stays pending, like the
                        # queue's own post-horizon events.
                        break
                    if not progressed:
                        idle_rounds += 1
                        # One idle round is legal (the absorb/rebalance
                        # runs NEXT iteration); repeated idleness means
                        # the fence can never clear.
                        if idle_rounds >= 3:
                            word = (FAULT_INGEST if feeder_live
                                    else FAULT_SPILL_STALL)
                            n_out = (int(pool_seqs.size) if pool_live
                                     else feeder.n - feeder.cursor)
                            raise EngineFaultError(
                                word, new_done,
                                detail=(f"{n_out} external event(s) "
                                        "outstanding but no segment can "
                                        "make progress"),
                            )
                    else:
                        idle_rounds = 0
                    continue
                if new_done < target:
                    # Loop exited before its batch target: drained, horizon,
                    # or admission fence with nothing outstanding — all
                    # terminal.
                    break
        return (state, queue, stats, pool_rows, pool_seqs, ingested, shed,
                seg_index, read)

    def run(self, state, *, until: float | None = None,
            max_batches: int | None = None,
            max_events: int | None = None,
            events: Sequence | None = None,
            arrivals=None,
            backpressure: str = "block",
            checkpoint_every: int | None = None,
            checkpoint_dir: str | None = None,
            resume_from: int | str | None = None,
            _segment_hook: Callable | None = None,
            _stream_prefetch: bool = True) -> RunResult:
        """Execute until the pending set drains (or a bound trips).

        ``until`` stops before any event later than it runs (identical
        horizon rule on every backend); ``max_batches`` bounds executed
        batches; ``max_events`` bounds executed events (host backends
        only — the device loop counts batches).  ``events`` optionally
        replaces the program's initial schedule for this run, as
        ``(time, type_name_or_id[, arg])`` tuples.

        ``arrivals`` opens the system (DESIGN.md §10): an
        :class:`repro.stream.ArrivalSource` streamed into the run in
        fixed blocks.  The result is bit-identical to pre-seeding the
        same trace (state, executed events, dropped, final_time) as
        long as neither run overflows; arrivals with ``time > until``
        are never consumed.  On the device backend blocks are absorbed
        at segment boundaries under the lex admission fence with
        double-buffered host→device staging; ``backpressure`` picks
        what happens when an admissible arrival finds the queue full:
        ``"block"`` (wait for capacity; a wedged topology raises
        ``FAULT_INGEST``), ``"shed"`` (drop it, counted in
        ``RunResult.shed``) or ``"error"`` (raise immediately).  With
        ``overflow='spill'`` the non-fitting remainder joins the spill
        pool instead (never sheds).  Device streaming requires
        ``queue_mode='tiered3'`` (+ ``queue_kernels='xla'`` on the
        single queue); host backends push the stream into the unbounded
        heap (only ``backpressure='block'`` is meaningful there).

        Device backends additionally run SEGMENTED: ``checkpoint_every=N``
        snapshots the full engine pytree (state, every queue tier, the
        cumulative stats carry) to ``checkpoint_dir`` every N super-steps
        through :class:`repro.checkpoint.manager.CheckpointManager`
        (async + atomic, off the hot path), and ``resume_from=step`` (or
        ``"latest"``) restores one and continues — a resumed run is
        bit-identical to an uninterrupted one because the while-loop
        carry IS the checkpoint (streamed runs snapshot the arrival
        cursor and ingest counters alongside it).  ``_segment_hook(
        seg_index, state, queue, stats)`` is the fault-injection seam:
        called between segments, it may return a replacement ``(state,
        queue, stats)`` triple (tests only).
        """
        t_end = float("inf") if until is None else float(until)
        if backpressure not in ("block", "shed", "error"):
            raise ValueError(
                f"backpressure must be 'block', 'shed' or 'error', "
                f"got {backpressure!r}"
            )
        if arrivals is None and backpressure != "block":
            raise ValueError(
                "backpressure= configures streamed runs — pass "
                "arrivals= as well"
            )
        evs = self._initial_events(events)
        if self.check != "off" and not self._check_done:
            self.program._run_check(self.check, state=state)
            self._check_done = True
        if self.backend == "device":
            if max_events is not None:
                raise ValueError(
                    "max_events is host-only; the device loop counts "
                    "batches — use max_batches"
                )
            return self._run_device(
                state, evs, t_end,
                (1 << 30) if max_batches is None else int(max_batches),
                checkpoint_every=checkpoint_every,
                checkpoint_dir=checkpoint_dir,
                resume_from=resume_from,
                segment_hook=_segment_hook,
                arrivals=arrivals,
                backpressure=backpressure,
                stream_prefetch=_stream_prefetch,
            )
        if (checkpoint_every is not None or checkpoint_dir is not None
                or resume_from is not None or _segment_hook is not None):
            raise ValueError(
                "checkpoint_every/checkpoint_dir/resume_from are "
                "device-backend knobs; the host backend would silently "
                "ignore them — drop them or build with backend='device'"
            )
        if arrivals is not None and backpressure != "block":
            raise ValueError(
                "host backends push the stream into an unbounded heap: "
                "backpressure='shed'/'error' can never trigger there — "
                "use the default 'block' or build backend='device'"
            )
        queue = HostEventQueue()
        for (t, type_id, arg) in evs:
            queue.push(t, type_id, arg)
        n_ingested = 0
        if arrivals is not None:
            # Host iterator path: seeds pushed first (seqs 0..n0-1),
            # then the stream in source order (seqs n0..) — exactly the
            # device reservation discipline, so the heap's (time, seq)
            # total order matches the closed pre-seeded run's.
            arrivals.seek(0)
            for block in arrivals.blocks():
                for row in np.asarray(block, np.float32):
                    if row[1] < 0:
                        continue
                    queue.push(float(row[0]), int(row[1]),
                               normalize_arg(row[2:]))
                    n_ingested += 1
        if self.variant == "unbatched":
            from repro.core.scheduler import run_unbatched

            state, rs = run_unbatched(
                self.program.host_registry(), state, queue,
                jit_handlers=self.jit_handlers,
                max_events=max_events, max_batches=max_batches,
                t_end=t_end,
            )
        else:
            state, rs = self.sched.run(
                state, queue, max_events=max_events,
                max_batches=max_batches, t_end=t_end,
            )
        return RunResult(
            state=state,
            events=rs.events_executed,
            batches=rs.batches_executed,
            dropped=0,
            final_time=float(rs.final_time),
            rollbacks=rs.rollbacks,
            raw=rs,
            ingested=n_ingested,
        )
