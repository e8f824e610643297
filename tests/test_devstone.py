"""DEVStone HI on the default device path (``bench/models/devstone.py``).

The engine, run to drain, against the plain reference
(``bench/reference/devstone.py``) and against the HI closed form; and
the rule that picks the window dispatch from the dense word count.
"""

import functools
import importlib.util
import json
import re
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import spans
from repro.core.codec import DenseCodec
from repro.core.composer import (
    WORD_COUNT_LIMIT,
    build_switch_dispatcher,
    default_dispatch_mode,
)
from repro.core.events import EventRegistry

BENCH = Path(__file__).resolve().parents[1] / "bench"


def _load(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


model = _load("models", "devstone")
ref = _load("reference", "devstone")


def _bench_cfg(name: str) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return {**cfg, **cfg.get("rehearsal", {})}


def _cfg(width: int, depth: int, injections: int = 3) -> dict:
    cfg = dict(width=width, depth=depth, prep_time=0.0,
               injections=injections, period=1.0, max_batch_len=16,
               max_emit=width)
    peak = ref.simulate(cfg, 1, 10**9, track_peak=True)["peak_pending"]
    return dict(cfg, capacity=peak + 16 * width)


@functools.cache
def _drained(width: int, depth: int, seed: int = 2**31 + 9):
    cfg = _cfg(width, depth)
    sim = model.program(cfg, seed).build(backend="device")
    res = sim.run(model.initial_state(cfg))
    return cfg, sim.engine, res, ref.simulate(cfg, seed, 10**9)


SIZES = [(2, 1), (4, 3), (5, 4)]


@pytest.mark.parametrize("width,depth", SIZES)
def test_engine_matches_reference_to_drain(width, depth):
    cfg, eng, res, want = _drained(width, depth)
    assert eng.dispatch_mode == "masked"
    got = model.observe(res.state)
    assert (res.events, res.batches, res.final_time) == (
        want["events"], want["batches"], want["final_time"])
    assert (res.emitted, res.pending, res.dropped) == (
        want["emitted"], 0, 0)
    np.testing.assert_array_equal(got["ext_count"], want["ext_count"])
    np.testing.assert_array_equal(got["int_count"], want["int_count"])
    assert (got["sink"], got["checksum"]) == (want["sink"],
                                              want["checksum"])


@pytest.mark.parametrize("width,depth", SIZES)
def test_counts_follow_the_hi_closed_form(width, depth):
    """A_{d,j} receives j messages an injection, A_{1,1} one; every
    message is one δext and one δint; D coupled inputs an injection."""
    cfg, _, res, _ = _drained(width, depth)
    n = cfg["injections"]
    want = np.zeros(model.num_atomics(width, depth), np.int64)
    for d in range(2, depth + 1):
        for j in range(1, width):
            want[(d - 2) * (width - 1) + j - 1] = j * n
    want[-1] = n
    got = model.observe(res.state)
    np.testing.assert_array_equal(got["ext_count"], want)
    np.testing.assert_array_equal(got["int_count"], want)
    assert got["sink"] == n
    per_injection = width * (width - 1) // 2 * (depth - 1) + 1
    assert want.sum() == per_injection * n
    assert res.type_counts.tolist() == [depth * n, want.sum(), want.sum()]
    assert res.events == (2 * per_injection + depth) * n


def test_word_count_picks_the_dispatch():
    assert WORD_COUNT_LIMIT == 4096
    assert default_dispatch_mode(1, 16) == "switch"     # PHOLD, 16 words
    assert default_dispatch_mode(3, 3) == "switch"      # admission, 39
    assert default_dispatch_mode(2, 11) == "switch"     # 4,094 words
    assert default_dispatch_mode(2, 12) == "masked"     # 8,190 words
    assert default_dispatch_mode(3, 16) == "masked"     # 64,570,080
    cfg = _bench_cfg("devstone-hi")
    assert model.dispatch_mode(cfg) == "masked"
    eng = model.program(cfg, 1).build(backend="device").engine
    assert eng.dispatch_mode == "masked"
    assert eng.codec.num_batches == 64_570_080


def test_bench_phold_and_admission_keep_the_switch():
    phold, admission = _load("models", "phold"), _load("models", "admission")
    cfg = _bench_cfg("phold-1m")
    assert phold.program(cfg, 1).build(
        backend="device").engine.dispatch_mode == "switch"
    cfg = _bench_cfg("admission-64")
    eng = admission.program(cfg, 16).build(backend="device",
                                           **cfg["build"]).engine
    assert eng.dispatch_mode == "switch"
    assert eng.codec.num_batches == 39


def test_switch_over_the_ceiling_raises_at_once():
    cfg = _bench_cfg("devstone-hi")
    prog = model.program(cfg, 1)
    t = time.perf_counter()
    with pytest.raises(ValueError, match="64,570,080 batch words"):
        prog.build(backend="device", dispatch_mode="switch")
    reg = EventRegistry()
    for name in ("a", "b"):
        reg.register(name, lambda s, t, a: s, lookahead=1.0)
    with pytest.raises(ValueError, match="8,190 batch words"):
        build_switch_dispatcher(reg, DenseCodec(2, 12))
    assert time.perf_counter() - t < 5.0
    assert callable(build_switch_dispatcher(reg, DenseCodec(2, 11)))


def test_masked_build_builds_no_switch(monkeypatch):
    """The masked default never builds the full switch, and its run
    stats carry the type mix but no word histogram."""
    import repro.core.engine as engine_mod

    def refuse(*args, **kwargs):
        raise AssertionError("the full switch was built")

    monkeypatch.setattr(engine_mod, "build_switch_dispatcher", refuse)
    cfg = _cfg(4, 3)
    assert model.program(cfg, 3).build(
        backend="device").engine.dispatch_mode == "masked"
    _, _, res, _ = _drained(4, 3)
    assert res.word_counts is None and res.type_counts is not None


def _ops(op):
    for region in op.regions:
        for block in region.blocks:
            for inner in block.operations:
                yield inner.operation
                yield from _ops(inner.operation)


def test_masked_lanes_sit_in_the_dispatch_scope():
    """The masked path's lane switches are named under ``des.dispatch``,
    so a trace reduction puts them there."""
    cfg, eng, _, _ = _drained(4, 3)
    low = eng.lower_run(model.initial_state(cfg), eng.initial_queue([]))
    names = [re.match(r'loc\("([^"]*)"', str(op.location))
             for op in _ops(low.compiler_ir().operation)
             if op.name == "stablehlo.case"]
    lanes = [m.group(1) for m in names
             if m and m.group(1).endswith(f"/{spans.DISPATCH}/cond")]
    assert len(lanes) == cfg["max_batch_len"], names
