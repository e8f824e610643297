"""Compile-time batch composition (paper §III-A, Alg. 1).

The paper composes, *during compilation*, one contiguous procedure per
batch identifier by concatenating the registered event handlers' bodies,
so the compiler optimizes across events.  The JAX equivalent: for each
batch word ``w = [t0, t1, ...]`` we build a Python closure that applies
the handlers sequentially and hand it to ``jax.jit`` — tracing inlines
all handler bodies into ONE jaxpr/HLO module, which XLA then optimizes as
a contiguous code fragment (cross-event DCE, fusion, CSE).  That is the
paper's mechanism with XLA in the role of clang.

Three composition strategies:

* :class:`EagerComposer` — paper-faithful: ALL batch programs are
  composed and AOT-compiled (``.lower().compile()``) up front, exactly
  like the C++ template instantiation.  Compile time grows with the
  batch count (reproduced as the Fig-4 benchmark).
* :class:`LazyComposer` — the paper's §IV.D JIT idea: programs are
  composed up front (cheap) but compiled on first dispatch and cached,
  so only batches that actually occur pay compilation cost.
* :func:`build_switch_dispatcher` — the TPU-native runtime: a single
  program containing ``lax.switch`` over every composed batch, used by
  the fully on-device scheduler (no host round-trip per batch).

On-device dispatch additionally comes in two specialized shapes
(DESIGN.md §7, selected by ``DeviceEngine(dispatch_mode=...)``):

* :func:`build_masked_dispatcher` — the generic per-handler-scope
  baseline: one masked per-lane ``lax.switch`` over the T event types
  (plus a no-op leg) per window lane.  Compile cost is O(T · max_len)
  regardless of the batch-word count, but XLA sees each handler alone —
  no cross-event scope.
* :func:`build_fused_dispatcher` — the two-level composition-
  specialized path: the top-W *hot* batch words are AOT-composed into
  straight-line "super-procedures" (no masks, no per-type legs —
  handlers inlined back-to-back exactly like the full switch's
  branches, so XLA fuses/DCEs across event boundaries), reached
  through a bounded ``lax.switch`` over W+1 branches, picked by
  comparing the word's code with the W hot codes; every other word
  falls back to the masked path.  Compile cost is W-linear (guarded by
  ``benchmarks/compile_times.py``), and because hot branches, full-
  switch branches, and the masked path all execute the identical
  handler sequence, all three modes are bit-identical
  (``tests/_parity.py``).

Handlers follow the conventions of :mod:`repro.core.events`.  Emitted
events are buffered and returned to the caller *after* the whole batch
has run — the paper's §IV.D "postponing the scheduling of all new events
to the end of a batch execution" optimization (always on here; the
unbatched baseline in benchmarks/ inserts eagerly).  Each buffered
emission carries the in-batch index of its emitting event, so schedulers
anchor the new event at the emitter's timestamp — results never depend
on how events were grouped into batches.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.events import ARG_WIDTH, EventRegistry, normalize_handler_result
from repro.core.codec import DenseCodec, PaperCodec, make_codec


# ---------------------------------------------------------------------------
# Host-side batch programs
# ---------------------------------------------------------------------------

def compose_word_fn(registry: EventRegistry, word: Sequence[int]) -> Callable:
    """Concatenate the handlers of ``word`` into one traceable function.

    Returns ``fn(state, ts, args) -> (state, emitted)`` where ``ts`` is a
    length-``len(word)`` sequence of timestamps and ``args`` the matching
    handler arguments.  ``emitted`` is the Python list of events created
    by any handler, in execution order (deferred scheduling, §IV.D), as
    ``(src, delay, type_id, arg)`` tuples where ``src`` is the index
    within the batch of the emitting event — schedulers anchor the new
    event at ``ts[src] + delay``, so emission times do not depend on how
    events were grouped into batches.
    """
    types = [registry[t] for t in word]

    def batch_fn(state, ts, args):
        emitted = []
        for i, et in enumerate(types):
            result = et.handler(state, ts[i], args[i])
            state, new = normalize_handler_result(
                result, returns_events=et.returns_events
            )
            emitted.extend((i, delay, ty, a) for (delay, ty, a) in new)
        return state, emitted

    batch_fn.__name__ = "batch_" + "_".join(t.name for t in types)
    return batch_fn


class _ComposerBase:
    """Shared bookkeeping for host-side composers."""

    def __init__(self, registry: EventRegistry, codec):
        if not registry.frozen:
            registry.freeze()
        self.registry = registry
        self.codec = codec
        self._programs: dict[int, Callable] = {}   # code -> jitted fn
        self._words: dict[int, tuple[int, ...]] = {}
        # Per-word execution histogram (code -> dispatch count): the
        # host-side profiling source for hot-word selection
        # (:func:`hot_words_from_counts`); the device engine keeps the
        # equivalent histogram in its run stats (``word_counts``).
        self.execute_counts: dict[int, int] = {}

    def word_for(self, code: int) -> tuple[int, ...]:
        if code not in self._words:
            self._words[code] = tuple(self.codec.decode(code))
        return self._words[code]

    def _build(self, code: int) -> Callable:
        word = self.word_for(code)
        fn = compose_word_fn(self.registry, word)
        # Timestamps are traced values (donated by the scheduler); the
        # batch structure itself is baked into the program — exactly the
        # paper's "batch = compiled contiguous procedure".
        return jax.jit(fn)

    def program(self, code: int) -> Callable:
        if code not in self._programs:
            self._programs[code] = self._build(code)
        return self._programs[code]

    def execute(self, code: int, state, ts, args):
        """Run batch ``code``; returns (state, emitted_events)."""
        self.execute_counts[code] = self.execute_counts.get(code, 0) + 1
        return self.program(code)(state, ts, args)

    @property
    def num_composed(self) -> int:
        return len(self._programs)

    @classmethod
    def from_program(cls, program, **kwargs):
        """Construct from a frozen SimProgram: the host-adapted registry
        plus a codec sized by the program's Config."""
        registry = program.host_registry()
        cfg = program.config
        codec = make_codec(cfg.codec, len(registry), cfg.max_batch_len)
        return cls(registry, codec, **kwargs)


class EagerComposer(_ComposerBase):
    """Paper-faithful: compose + AOT-compile every batch up front.

    ``state_spec``/``arg_spec`` are ShapeDtypeStruct pytrees describing
    one state and one handler argument; they let us `.lower().compile()`
    without touching device memory (same trick as the multi-pod dry-run).
    """

    def __init__(self, registry, codec, *, state_spec=None, arg_spec=None,
                 aot: bool = True):
        super().__init__(registry, codec)
        self.aot = aot and state_spec is not None
        self.state_spec = state_spec
        self.arg_spec = arg_spec
        for code in codec.enumerate_codes():
            word = self.word_for(code)
            if not word:
                continue  # redundant ν-only code (PaperCodec)
            if self.aot:
                self._programs[code] = self._aot_build(code, word)
            else:
                self._programs[code] = self._build(code)

    def _aot_build(self, code, word):
        fn = compose_word_fn(self.registry, word)
        k = len(word)
        ts_spec = [jax.ShapeDtypeStruct((), jnp.float32)] * k
        args_spec = [self.arg_spec] * k
        return jax.jit(fn).lower(self.state_spec, ts_spec, args_spec).compile()

    def execute(self, code, state, ts, args):
        self.execute_counts[code] = self.execute_counts.get(code, 0) + 1
        prog = self._programs[code]
        if self.aot:
            return prog(state, list(ts), list(args))
        return prog(state, ts, args)


class LazyComposer(_ComposerBase):
    """Beyond-paper (§IV.D): compile batches on first occurrence only."""
    # program() already builds lazily; nothing else needed.


# ---------------------------------------------------------------------------
# On-device dispatchers (TPU-native runtime, DESIGN.md §2 and §7)
# ---------------------------------------------------------------------------

# The most dense batch words (Σ|Σ|^i, i = 1..max_len) the full switch is
# built for.  Two uses: a device build that names no dispatch takes the
# switch at or below it and the masked per-lane path above it (three
# types at 16 lanes are 64,570,080 words, each a branch to trace), and
# the engine carries its per-word histogram (``word_counts``,
# i32[num_batches]) only at or below it, so the loop carry stays small.
WORD_COUNT_LIMIT = 4096


def default_dispatch_mode(num_types: int, max_len: int) -> str:
    """The dispatch a device build takes when none is named:
    ``"switch"`` while the dense word count is at most
    :data:`WORD_COUNT_LIMIT`, else ``"masked"``."""
    words = DenseCodec(num_types, max_len).num_batches
    return "switch" if words <= WORD_COUNT_LIMIT else "masked"


def _emit_layout(max_len: int, max_emit: int):
    """Shared on-device emit-block layout: ``emits`` is
    ``f32[max_len * max_emit, 2 + ARG_WIDTH]`` rows of
    ``(time, type, arg...)``, event ``i`` owning rows
    ``[i*max_emit, (i+1)*max_emit)``; ``type == -1`` marks empty slots.
    Every dispatcher flavor writes this exact layout, which is what
    makes them interchangeable (and bit-comparable) to the engine."""
    emit_rows = max_len * max_emit
    emit_width = 2 + ARG_WIDTH

    def empty_emits():
        e = jnp.zeros((emit_rows, emit_width), jnp.float32)
        return e.at[:, 1].set(-1.0)

    return emit_rows, emit_width, empty_emits


def make_word_branch(registry: EventRegistry, word: Sequence[int], *,
                     max_emit: int, emit_width: int,
                     empty_emits: Callable) -> Callable:
    """The composed straight-line program of one batch word: handlers
    applied back-to-back with no masks or per-type legs, each emitting
    into its own fixed row block — the paper's contiguous batch
    procedure.  Used verbatim as a full-switch branch AND as a fused
    hot-word super-procedure."""
    types = [registry[t] for t in word]

    def branch(state, ts, args):
        emits = empty_emits()
        for i, et in enumerate(types):
            result = et.handler(state, ts[i], args[i])
            if et.returns_events:
                state, new = result
                new = jnp.asarray(new, jnp.float32)
                if new.shape != (max_emit, emit_width):
                    raise ValueError(
                        f"on-device handler {et.name} must emit "
                        f"f32[{max_emit}, {emit_width}], got {new.shape}"
                    )
                emits = jax.lax.dynamic_update_slice(
                    emits, new, (i * max_emit, 0)
                )
            else:
                state = result
        return state, emits

    return branch


def _require_dense(codec, what: str):
    if not isinstance(codec, DenseCodec):
        raise TypeError(
            f"{what} requires the DenseCodec (contiguous ids); "
            "the PaperCodec's redundant ids would blow up the switch."
        )


def build_switch_dispatcher(
    registry: EventRegistry,
    codec: DenseCodec,
    *,
    max_emit: int = 2,
):
    """One traceable function dispatching over ALL composed batches.

    The returned ``dispatch(code, state, ts, types, args)`` contains a
    ``lax.switch`` whose branch ``c`` is the composed program of batch
    word ``decode(c)``.  All branches share the padded signature

        ts:    f32[max_len]          event timestamps
        types: i32[max_len]          event type ids (engine bookkeeping)
        args:  f32[max_len, ARG_WIDTH]

    and return ``(state, emits)`` with
    ``emits: f32[max_len * max_emit, 2 + ARG_WIDTH]`` rows of
    ``(time, type, arg...)``; ``type == -1`` marks an empty slot.

    On-device handlers must follow the fixed-record convention
    (DESIGN.md §6.3): ``handler(state, t, arg) -> state`` or
    ``(state, emits_f32[max_emit, 2+ARG_WIDTH])``.

    Because every branch lives in one XLA module, XLA optimizes each
    batch body as a contiguous fragment — the paper's cross-event scope —
    while the simulation main loop never leaves the device.
    """
    _require_dense(codec, "on-device dispatch")
    if codec.num_batches > WORD_COUNT_LIMIT:
        raise ValueError(
            f"the full switch over {codec.num_types} types and "
            f"{codec.max_len} lanes has {codec.num_batches:,} batch "
            f"words, more than the {WORD_COUNT_LIMIT:,} it is built for; "
            "use dispatch_mode='masked', or name no dispatch_mode and "
            "the build picks it"
        )
    if not registry.frozen:
        registry.freeze()
    max_len = codec.max_len
    emit_rows, emit_width, _empty_emits = _emit_layout(max_len, max_emit)

    branches = []
    for code, word in codec.enumerate_words():
        del code
        branches.append(make_word_branch(
            registry, word, max_emit=max_emit, emit_width=emit_width,
            empty_emits=_empty_emits,
        ))

    def dispatch(code, state, ts, types, args):
        del types  # engine bookkeeping only; the word is baked per branch
        return jax.lax.switch(code, branches, state, ts, args)

    dispatch.num_batches = codec.num_batches
    dispatch.max_len = max_len
    dispatch.max_emit = max_emit
    dispatch.emit_rows = emit_rows
    dispatch.emit_width = emit_width
    # Layout helper for callers (e.g. the engine's vmapped run path and
    # the bulk scatter insert) that need a no-emission block.
    dispatch.empty_emits = _empty_emits
    return dispatch


def build_masked_dispatcher(
    registry: EventRegistry,
    codec: DenseCodec,
    *,
    max_emit: int = 2,
):
    """The generic masked window path: per-handler compiler scope.

    ``dispatch(state, ts, types, args, length) -> (state, emits)``
    applies, for each lane ``i < max_len``, a masked ``lax.switch`` over
    the T registered handlers plus a no-op leg (selected for padding
    lanes ``i >= length``).  Emitting handlers write their rows at
    ``i * max_emit`` — byte-identical emit layout to the composed word
    branches, and the handler sequence for any window is identical too,
    so this path is bit-equivalent to the full switch while compiling
    only O(T · max_len) handler bodies instead of Σ Tᵏ.

    This is the XLA analog of the paper's per-handler dispatch baseline
    (each handler is optimized alone; no cross-event scope) and the
    fallback leg of :func:`build_fused_dispatcher`.
    """
    _require_dense(codec, "on-device dispatch")
    if not registry.frozen:
        registry.freeze()
    max_len = codec.max_len
    num_types = len(registry)
    emit_rows, emit_width, _empty_emits = _emit_layout(max_len, max_emit)

    def make_lane_legs(i):
        def make_leg(et):
            def leg(state, emits, ts, args):
                result = et.handler(state, ts[i], args[i])
                if et.returns_events:
                    state, new = result
                    new = jnp.asarray(new, jnp.float32)
                    if new.shape != (max_emit, emit_width):
                        raise ValueError(
                            f"on-device handler {et.name} must emit "
                            f"f32[{max_emit}, {emit_width}], got {new.shape}"
                        )
                    emits = jax.lax.dynamic_update_slice(
                        emits, new, (i * max_emit, 0)
                    )
                else:
                    state = result
                return state, emits

            return leg

        def noop(state, emits, ts, args):
            del ts, args
            return state, emits

        return [make_leg(registry[t]) for t in range(num_types)] + [noop]

    lane_legs = [make_lane_legs(i) for i in range(max_len)]

    def dispatch(state, ts, types, args, length):
        emits = _empty_emits()
        for i in range(max_len):
            idx = jnp.where(
                jnp.int32(i) < length,
                jnp.clip(types[i], 0, num_types - 1),
                jnp.int32(num_types),
            )
            state, emits = jax.lax.switch(
                idx, lane_legs[i], state, emits, ts, args
            )
        return state, emits

    dispatch.num_batches = codec.num_batches
    dispatch.max_len = max_len
    dispatch.max_emit = max_emit
    dispatch.emit_rows = emit_rows
    dispatch.emit_width = emit_width
    dispatch.empty_emits = _empty_emits
    return dispatch


def build_fused_dispatcher(
    registry: EventRegistry,
    codec: DenseCodec,
    hot_words: Sequence[Sequence[int]],
    *,
    max_emit: int = 2,
):
    """Two-level composition-specialized dispatch (DESIGN.md §7).

    The W declared/profiled *hot* batch words are composed into
    straight-line super-procedures (:func:`make_word_branch` — the same
    fused bodies the full switch uses, so XLA optimizes across event
    boundaries, the paper's §III scope win) and reached through a
    bounded ``lax.switch`` over W+1 branches: a Horner code equal to
    the code of hot word ``i`` takes slot ``i``, and every other code
    slot W, the generic masked path (:func:`build_masked_dispatcher`).
    The lookup compares against the W hot codes, so it costs the same
    at any word count.

    ``dispatch(code, state, ts, types, args, length) -> (state, emits)``.
    Compile cost is W-linear plus the constant masked fallback
    (``benchmarks/compile_times.py`` guards this); results are
    bit-identical to both other modes for every window, hot or not.

    Attributes: ``hot_words`` (the deduplicated tuple actually baked
    in), ``num_hot``, ``hot_codes`` (their Horner codes, slot order),
    ``slot_of`` (code → slot; ``num_hot`` = fallback), plus the shared
    layout attrs.
    """
    _require_dense(codec, "fused dispatch")
    if not registry.frozen:
        registry.freeze()
    max_len = codec.max_len
    num_types = len(registry)
    emit_rows, emit_width, _empty_emits = _emit_layout(max_len, max_emit)

    seen: dict[tuple[int, ...], None] = {}
    for w in hot_words:
        word = tuple(int(t) for t in w)
        if not 1 <= len(word) <= max_len:
            raise ValueError(
                f"hot word {word} has length {len(word)}; expected "
                f"1..{max_len} (= max_batch_len)"
            )
        for t in word:
            if not 0 <= t < num_types:
                raise ValueError(
                    f"hot word {word} names type id {t}; registry has "
                    f"{num_types} types"
                )
        seen.setdefault(word, None)
    hot = tuple(seen)

    fallback = build_masked_dispatcher(registry, codec, max_emit=max_emit)

    def make_hot(word):
        branch = make_word_branch(
            registry, word, max_emit=max_emit, emit_width=emit_width,
            empty_emits=_empty_emits,
        )

        def hot_branch(state, ts, types, args, length):
            del types, length  # the word (and its length) is baked in
            return branch(state, ts, args)

        return hot_branch

    def fallback_branch(state, ts, types, args, length):
        return fallback(state, ts, types, args, length)

    branches = [make_hot(w) for w in hot] + [fallback_branch]

    num_hot = len(hot)
    hot_codes = np.asarray([codec.encode(list(w)) for w in hot], np.int32)
    codes_j = jnp.asarray(hot_codes)
    slots_j = jnp.arange(num_hot, dtype=jnp.int32)

    def slot_of(code):
        return jnp.min(jnp.where(codes_j == code, slots_j, num_hot),
                       initial=num_hot)

    def dispatch(code, state, ts, types, args, length):
        return jax.lax.switch(slot_of(code), branches, state, ts, types,
                              args, length)

    dispatch.hot_words = hot
    dispatch.num_hot = num_hot
    dispatch.hot_codes = hot_codes
    dispatch.slot_of = slot_of
    dispatch.num_batches = codec.num_batches
    dispatch.max_len = max_len
    dispatch.max_emit = max_emit
    dispatch.emit_rows = emit_rows
    dispatch.emit_width = emit_width
    dispatch.empty_emits = _empty_emits
    return dispatch


def hot_words_from_counts(counts, codec, top_w: int):
    """Top-W batch words by observed frequency — the profile half of
    "profile or statically declare".

    ``counts`` is either the device engine's per-word histogram
    (``RunResult.word_counts`` / run stats ``word_counts``, an array
    over dense codes) or a host composer's ``execute_counts`` dict.
    Returns a list of word tuples suitable for
    ``DeviceEngine(hot_words=...)`` / ``build(..., hot_words=...)``;
    ties break toward the smaller code so the selection is
    deterministic.  Words never observed are never selected.
    """
    if hasattr(counts, "items"):
        pairs = list(counts.items())
    else:
        pairs = list(enumerate(np.asarray(counts).reshape(-1).tolist()))
    ranked = sorted(
        ((int(n), int(code)) for code, n in pairs if int(n) > 0),
        key=lambda p: (-p[0], p[1]),
    )
    return [tuple(codec.decode(code)) for _, code in ranked[:int(top_w)]]
