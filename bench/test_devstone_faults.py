"""The comparison that decides ``correct`` catches a broken DEVStone run.

As ``test_cells_faults.py`` does for the PHOLD and admission cells: each
test plants one fault under the timed path at the rehearsal sizes and
sees ``correct`` come out false, and the control -- the reference with
its tie order broken, in the program's place -- fails too.
"""

from __future__ import annotations

import jax.numpy as jnp
import pytest

import harness
from repro.core.engine import DeviceEngine
from test_cells_faults import half_window, run_tiny, state_unchanged

NAME = "devstone-hi.closed"


def one_ext_dropped(monkeypatch):
    """The coupled input C_1 loses its message to A_{1,1}: one EXT an
    injection is never emitted."""
    cfg = harness.load_cell(NAME, tiny=True).cfg
    model = harness.load_module("models", cfg["model"])
    sink_atomic = model.num_atomics(cfg["width"], cfg["depth"]) - 1
    orig = DeviceEngine._dispatch_window

    def dispatch(self, state, ts, tys, args, length):
        state, emits = orig(self, state, ts, tys, args, length)
        lost = (emits[:, 1] == model.EXT) & (emits[:, 2] == sink_atomic)
        return state, emits.at[:, 1].set(jnp.where(lost, -1.0, emits[:, 1]))

    monkeypatch.setattr(DeviceEngine, "_dispatch_window", dispatch)


@pytest.mark.parametrize("fault", [state_unchanged, half_window,
                                   one_ext_dropped])
def test_fault_comes_out_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    line = run_tiny(NAME)
    assert not line["correct"]
    assert line["failed"] >= 1


@pytest.mark.parametrize("seed", [1, 2**31 + 1, 3_000_000_001])
def test_control_comes_out_not_correct(seed):
    cfg = harness.load_cell(NAME, tiny=True).cfg
    ref = harness.load_module("reference", cfg["model"])
    want = ref.simulate(cfg, seed, 20)
    got = ref.simulate(cfg, seed, 20, control=True)
    out = ref.compare(dict(got, seeded=cfg["injections"]), want)
    assert any(not v <= lim for (_, v, lim) in out), out


def test_model_draws_match_the_reference():
    """The model's injection times and checksum hash against the
    reference's, on large seeds."""
    import numpy as np

    cfg = harness.load_cell(NAME).cfg
    model = harness.load_module("models", cfg["model"])
    ref = harness.load_module("reference", cfg["model"])
    for seed in (7, 2**31 + 7, 3_000_000_001):
        times = model.injection_times(cfg, seed)
        assert times.tolist() == ref.injection_times(cfg, seed)
    assert (model.injection_times(cfg, 7) != times).any()
    keys = np.arange(0, 3 * 9802, 97, dtype=np.int32)
    bits = jnp.asarray(np.float32(times[:1]).view(np.uint32)).repeat(
        keys.size)
    got = model._mix(bits, jnp.asarray(keys))
    want = [ref._mix(ref.f32_bits(float(times[0])), int(k)) for k in keys]
    assert np.asarray(got).tolist() == want
