"""Composition-specialized dispatch (DESIGN.md §7).

Pins the tentpole contracts:

* the three dispatch modes (switch / masked / fused) are bit-equivalent
  at the dispatcher level AND over whole runs, hot word or fallback;
* the hot-set plumbing — slot table, default hot set, name resolution
  through ``SimProgram.build``, profiling via ``word_counts`` /
  ``hot_words_from_counts``;
* the knob validation (mode typos, hot_words outside fused mode, host
  misdirection).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.codec import DenseCodec
from repro.core.composer import (
    build_fused_dispatcher,
    build_masked_dispatcher,
    build_switch_dispatcher,
    hot_words_from_counts,
)
from repro.core.engine import DeviceEngine
from repro.core.events import ARG_WIDTH, EventRegistry, emits_events

from repro import poc
from repro.core.program import Config


def _two_type_registry():
    """inc emits nothing; spawn emits one event (exercising the emit
    rows that dispatch must place identically across modes)."""
    reg = EventRegistry()

    def inc(state, t, arg):
        return state + jnp.float32(1.0) + arg[0]

    @emits_events
    def spawn(state, t, arg):
        emit = jnp.zeros((1, 2 + ARG_WIDTH), jnp.float32)
        emit = emit.at[0, 0].set(t + 1.5)
        emit = emit.at[0, 1].set(0.0)
        return state * jnp.float32(2.0), emit

    reg.register("inc", inc, lookahead=1.0)
    reg.register("spawn", spawn, lookahead=1.0)
    return reg.freeze()


def _rand_windows(rng, codec, n, arg_width):
    """Random (ts, types, args, length) windows spanning every word."""
    out = []
    for code in range(codec.num_batches):
        word = tuple(codec.decode(code))
        length = len(word)
        k = codec.max_len
        tys = np.zeros((k,), np.int32)
        tys[:length] = word
        ts = np.sort(rng.uniform(0, 5, k)).astype(np.float32)
        args = rng.uniform(0, 1, (k, arg_width)).astype(np.float32)
        out.append((
            jnp.asarray(ts), jnp.asarray(tys), jnp.asarray(args),
            jnp.int32(length),
        ))
    for _ in range(n):
        out.append(out[rng.integers(0, codec.num_batches)])
    return out


def test_three_modes_bit_equivalent_per_window():
    reg = _two_type_registry()
    codec = DenseCodec(num_types=2, max_len=3)
    sw = build_switch_dispatcher(reg, codec, max_emit=1)
    ma = build_masked_dispatcher(reg, codec, max_emit=1)
    fu = build_fused_dispatcher(
        reg, codec, [(0,), (0, 0), (1, 0)], max_emit=1
    )
    rng = np.random.default_rng(0)
    state0 = jnp.float32(3.0)
    for ts, tys, args, length in _rand_windows(rng, codec, 5, 4):
        code = codec.encode_jnp(tys, length)
        s_sw, e_sw = sw(code, state0, ts, tys, args)
        s_ma, e_ma = ma(state0, ts, tys, args, length)
        s_fu, e_fu = fu(code, state0, ts, tys, args, length)
        np.testing.assert_array_equal(np.asarray(s_sw), np.asarray(s_ma))
        np.testing.assert_array_equal(np.asarray(s_sw), np.asarray(s_fu))
        np.testing.assert_array_equal(np.asarray(e_sw), np.asarray(e_ma))
        np.testing.assert_array_equal(np.asarray(e_sw), np.asarray(e_fu))


def test_hot_slot_table():
    reg = _two_type_registry()
    codec = DenseCodec(num_types=2, max_len=2)
    hot = [(1,), (0, 1)]
    fu = build_fused_dispatcher(reg, codec, hot, max_emit=1)
    assert fu.hot_words == ((1,), (0, 1))
    assert fu.num_hot == 2
    assert fu.hot_codes.tolist() == [codec.encode(list(w)) for w in hot]
    for code in range(codec.num_batches):
        word = tuple(codec.decode(code))
        slot = int(fu.slot_of(jnp.int32(code)))
        if word in hot:
            assert slot == hot.index(word)
        else:
            assert slot == len(hot)  # fallback slot


def test_fused_validates_hot_words():
    reg = _two_type_registry()
    codec = DenseCodec(num_types=2, max_len=2)
    with pytest.raises(ValueError):
        build_fused_dispatcher(reg, codec, [(0, 0, 0)])  # too long
    with pytest.raises(ValueError):
        build_fused_dispatcher(reg, codec, [(5,)])       # bad type id
    with pytest.raises(ValueError):
        build_fused_dispatcher(reg, codec, [()])         # empty word
    # Duplicates collapse rather than error.
    fu = build_fused_dispatcher(reg, codec, [(0,), (0,)], max_emit=1)
    assert fu.num_hot == 1


def test_default_hot_set_covers_small_alphabets():
    """num_batches <= 32: the default hot set is the whole code space,
    so the fallback leg is dead and fused degenerates to a (reordered)
    full switch."""
    prog = poc.build_program(iters=8, config=Config(max_batch_len=3))
    prog.schedule(0.0, "Increment")
    sim = prog.build(backend="device", dispatch_mode="fused")
    eng = sim.engine
    assert eng.dispatch_mode == "fused"
    assert len(eng.hot_words) == eng.codec.num_batches
    slots = [int(eng.dispatch.slot_of(jnp.int32(c)))
             for c in range(eng.codec.num_batches)]
    assert max(slots) < len(eng.hot_words)


def test_word_counts_match_batches_and_composition():
    types = [0, 1, 0, 0, 1, 1, 0, 0, 1]

    def build(**kw):
        prog = poc.build_program(iters=8, config=Config(max_batch_len=3))
        for t, ty in enumerate(types):
            prog.schedule(float(t), ("Increment", "Set")[ty])
        return prog.build(backend="device", **kw)

    base = build().run(poc.initial_state())
    assert base.word_counts is not None
    assert int(base.word_counts.sum()) == base.batches
    # Identical composition histogram across dispatch modes.
    for mode in ("masked", "fused"):
        r = build(dispatch_mode=mode).run(poc.initial_state())
        np.testing.assert_array_equal(r.word_counts, base.word_counts)
    # The histogram counts real words: every nonzero code decodes to a
    # word no longer than max_batch_len.
    eng = build().engine
    for code in np.nonzero(base.word_counts)[0]:
        word = tuple(eng.codec.decode(int(code)))
        assert 1 <= len(word) <= 3


def test_hot_words_from_counts_ranking():
    codec = DenseCodec(num_types=2, max_len=2)
    counts = np.zeros((codec.num_batches,), np.int64)
    counts[codec.encode([0, 1])] = 5
    counts[codec.encode([1])] = 9
    counts[codec.encode([0])] = 5
    got = hot_words_from_counts(counts, codec, 2)
    assert got[0] == (1,)
    # tie between (0,) and (0,1) breaks toward the smaller code: (0,).
    assert got[1] == (0,)
    # dict input (host composer execute_counts) works too.
    got2 = hot_words_from_counts(
        {int(codec.encode([1])): 9, int(codec.encode([0])): 5}, codec, 8
    )
    assert got2 == [(1,), (0,)]


def test_hot_words_by_name_through_build():
    types = [0, 0, 1, 0]

    def build(**kw):
        prog = poc.build_program(iters=8, config=Config(max_batch_len=2))
        for t, ty in enumerate(types):
            prog.schedule(float(t), ("Increment", "Set")[ty])
        return prog.build(backend="device", **kw)

    base = build().run(poc.initial_state())
    hot = build(
        dispatch_mode="fused",
        hot_words=[("Increment", "Increment"), ("Set",)],
    )
    assert hot.engine.hot_words == ((0, 0), (1,))
    r = hot.run(poc.initial_state())
    assert int(r.state) == int(base.state)
    assert r.batches == base.batches


def test_knob_validation():
    reg = _two_type_registry()
    with pytest.raises(ValueError, match="dispatch_mode"):
        DeviceEngine(registry=reg, max_batch_len=2, capacity=32,
                     dispatch_mode="vectorized")
    with pytest.raises(ValueError, match="hot_words"):
        DeviceEngine(registry=reg, max_batch_len=2, capacity=32,
                     hot_words=[(0,)])  # only valid with fused
    with pytest.raises(ValueError, match="queue_kernels"):
        DeviceEngine(registry=reg, max_batch_len=2, capacity=32,
                     queue_kernels="cuda")
    prog = poc.build_program(iters=4)
    prog.schedule(0.0, "Increment")
    with pytest.raises(ValueError, match="dispatch_mode"):
        prog.build(backend="host", scheduler="conservative",
                   state_spec=jnp.zeros((), jnp.uint32),
                   dispatch_mode="fused")


def test_dispatch_attr_always_available():
    """benchmarks/device_engine.py probes eng.dispatch directly — it
    must exist (and work) in every dispatch mode."""
    prog = poc.build_program(iters=8, config=Config(max_batch_len=2))
    prog.schedule(0.0, "Increment")
    for mode in ("switch", "masked", "fused"):
        eng = prog.build(backend="device", dispatch_mode=mode).engine
        assert callable(eng.dispatch)
        assert eng.dispatch.num_batches == eng.codec.num_batches
