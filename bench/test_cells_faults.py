"""The comparison that decides ``correct`` catches a broken timed path.

Each test skips the harness's look for a chip, plants one fault under
the timed path at the tiny rehearsal sizes, drives the rest of a run,
and sees ``correct`` come out false.  The control -- the plain
reference with its ordering guarantee broken, in the program's place --
must fail too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import control
import harness
from repro.core.engine import DeviceEngine

ONE_CHIP = ["phold-1m.closed", "admission-64.streamed"]


def run_tiny(name: str, seed: int = 2**31 + 11) -> dict:
    cell = harness.load_cell(name, tiny=True)
    devices = harness.chips(cell.chips, require_tpu=False)
    return harness.run_cell(cell, seed=seed, seconds=0.5, trace=False,
                            devices=devices, t_start=0.0)


def state_unchanged(monkeypatch):
    orig = DeviceEngine.run

    def run(self, state, queue, **kw):
        _, queue, stats = orig(self, state, queue, **kw)
        return state, queue, stats

    monkeypatch.setattr(DeviceEngine, "run", run)


def half_window(monkeypatch):
    orig = DeviceEngine._dispatch_window

    def dispatch(self, state, ts, tys, args, length):
        return orig(self, state, ts, tys, args, (length + 1) // 2)

    monkeypatch.setattr(DeviceEngine, "_dispatch_window", dispatch)


def answer_altered(monkeypatch):
    orig = DeviceEngine._dispatch_window

    def dispatch(self, state, ts, tys, args, length):
        state, emits = orig(self, state, ts, tys, args, length)
        bump = (length > 0).astype(jnp.int32)
        state = jax.tree.map(
            lambda x: x + bump.astype(x.dtype)
            if x.ndim == 0 and jnp.issubdtype(x.dtype, jnp.integer) else x,
            state)
        return state, emits

    monkeypatch.setattr(DeviceEngine, "_dispatch_window", dispatch)


@pytest.mark.parametrize("name", ONE_CHIP)
@pytest.mark.parametrize("fault", [state_unchanged, half_window,
                                   answer_altered])
def test_fault_comes_out_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    line = run_tiny(name)
    assert not line["correct"]
    assert line["failed"] >= 1


@pytest.mark.parametrize("name,size", [("phold-1m.closed", 200),
                                       ("admission-64.streamed", 3000)])
def test_control_comes_out_not_correct(name, size):
    cell = harness.load_cell(name, tiny=True)
    for seed in (1, 2**31 + 1, 2**31 + 2):
        out = control.readings(cell, seed, size)
        assert any(not v <= lim for (_, v, lim) in out), out
