"""DEVStone HI as the engine runs it: the benchmark's own model.

The HI model of the DEVStone benchmark (Wainer et al. 2011; re-run
across DEVS simulators by Risco-Martín et al. 2024, arXiv 2402.05483),
as three event types over one atomic per entity id (``arg[0]``):

- ``IN`` (arg: level d): the coupled model C_d's input.  At d >= 2 it
  sends one message to each of C_d's W-1 atomics and one to C_{d-1}'s
  input; at d = 1 one to the single atomic A_{1,1};
- ``EXT`` (arg: atomic): δext counts the message and schedules the
  atomic's ``INT`` after ``prep_time``;
- ``INT`` (arg: atomic): λ + δint counts the transition and sends one
  ``EXT`` to the next atomic of its level's chain (the HI coupling), or,
  at A_{1,1}, one message to the sink.

Atomic A_{d,j} has id ``(d-2)(W-1) + j-1`` for d >= 2, and A_{1,1} the
last id.  Every handler folds ``mix(time bits, 3*entity + type)`` into an
order-sensitive checksum, as ``bench/reference/devstone.py`` does in
plain Python.  With three types and 16-event windows there are
64,570,080 batch words, so the default build dispatches lane by lane
(:func:`repro.core.composer.default_dispatch_mode`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.composer import default_dispatch_mode
from repro.core.program import EMIT_WIDTH, Config, SimProgram

IN, EXT, INT = 0, 1, 2
GRID = 256


def num_atomics(width: int, depth: int) -> int:
    return (depth - 1) * (width - 1) + 1


def dispatch_mode(cfg: dict) -> str:
    """The dispatch the default build takes for this model."""
    return default_dispatch_mode(3, cfg["max_batch_len"])


def injection_times(cfg: dict, seed: int) -> np.ndarray:
    """``f32[injections]``: injection ``i`` at ``i * period + u_i``,
    ``u_i`` drawn from ``seed`` on a 1/256 grid in [0, 0.5)."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, GRID // 2, size=cfg["injections"])
    i = np.arange(cfg["injections"])
    return (i * cfg["period"] + u / GRID).astype(np.float32)


def _mix(bits, key):
    h = (bits * jnp.uint32(2654435761)
         + key.astype(jnp.uint32) * jnp.uint32(40503)
         + jnp.uint32(12345))
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0x5BD1E995)
    return h ^ (h >> 15)


def program(cfg: dict, seed: int) -> SimProgram:
    """The HI program with its seeded injections scheduled."""
    width, depth = cfg["width"], cfg["depth"]
    rows = cfg["max_emit"]
    if width < 2 or rows < width:
        raise ValueError(f"HI needs width >= 2 and max_emit >= width "
                         f"(got width {width}, max_emit {rows})")
    prep = float(np.float32(cfg["prep_time"]))
    sink_atomic = num_atomics(width, depth) - 1
    lane = jnp.arange(rows)
    blank = jnp.full((rows, EMIT_WIDTH), -1.0, jnp.float32)
    prog = SimProgram(
        "devstone-hi",
        config=Config(max_batch_len=cfg["max_batch_len"],
                      capacity=cfg["capacity"], max_emit=rows),
    )

    def touch(state, t, ty, x):
        h = _mix(jax.lax.bitcast_convert_type(t, jnp.uint32), 3 * x + ty)
        return dict(state, checksum=state["checksum"] * jnp.uint32(31) + h)

    def send(delay, ty, x, on):
        """``rows`` emit rows: ``(delay, ty, x)`` where ``on``."""
        row = (jnp.zeros((rows, EMIT_WIDTH), jnp.float32)
               .at[:, 0].set(delay).at[:, 1].set(ty).at[:, 2].set(x))
        return jnp.where(on[:, None], row, blank)

    @prog.handler("IN", lookahead=0.0, emits=True)
    def coupled_in(state, t, arg):
        d = arg[0].astype(jnp.int32)
        state = touch(state, t, IN, d)
        inner = d >= 2
        to_in = inner & (lane == width - 1)
        to_ext = jnp.where(inner, lane < width - 1, lane == 0)
        x = jnp.where(to_in, d - 1,
                      jnp.where(inner, (d - 2) * (width - 1) + lane,
                                sink_atomic))
        ty = jnp.where(to_in, IN, EXT)
        return state, send(0.0, ty, x, to_in | to_ext)

    @prog.handler("EXT", lookahead=prep, emits=True)
    def external(state, t, arg):
        a = arg[0].astype(jnp.int32)
        state = touch(state, t, EXT, a)
        state["ext_count"] = state["ext_count"].at[a].add(1)
        return state, send(prep, INT, a, lane == 0)

    @prog.handler("INT", lookahead=0.0, emits=True)
    def internal(state, t, arg):
        a = arg[0].astype(jnp.int32)
        state = touch(state, t, INT, a)
        state["int_count"] = state["int_count"].at[a].add(1)
        at_sink = a == sink_atomic
        state["sink"] = state["sink"] + at_sink.astype(jnp.int32)
        chained = ~at_sink & (a % (width - 1) < width - 2)
        return state, send(0.0, EXT, a + 1, (lane == 0) & chained)

    for t in injection_times(cfg, seed):
        prog.schedule(float(t), "IN", arg=[float(depth)])
    return prog


def initial_state(cfg: dict):
    n = num_atomics(cfg["width"], cfg["depth"])
    return {
        "ext_count": jnp.zeros((n,), jnp.int32),
        "int_count": jnp.zeros((n,), jnp.int32),
        "sink": jnp.int32(0),
        "checksum": jnp.uint32(1),
    }


def observe(state) -> dict:
    """The host copy of the state the reference is compared with."""
    return {"ext_count": state["ext_count"], "int_count": state["int_count"],
            "sink": int(state["sink"]), "checksum": int(state["checksum"])}
