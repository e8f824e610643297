"""PHOLD as the engine runs it: the benchmark's own copy of the model.

ROSS's PHOLD model (``models/phold`` of the ROSS simulator) in the
engine's API: every LP starts ``start_events`` events at
``lookahead + Exp(mean)``; an executed event goes, with probability
``remote``, to an LP drawn uniformly from all LPs, else back to its own
LP, at ``now + lookahead + Exp(mean)``.

The draws are a counter hash of ``(time bits, lp)``, and the exponential
is read from a table of its ``2**16`` mid-point quantiles in f32, so the
device's f32 arithmetic and the plain reference
(``bench/reference/phold.py``) agree bit for bit: each time is one f32
sum, correctly rounded on both sides.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import ARG_WIDTH, Config, SimProgram

TABLE_BITS = 16


def exp_table(mean: float) -> np.ndarray:
    """``f32[2**16]``: the mid-point quantiles of ``Exp(mean)``."""
    u = (np.arange(1 << TABLE_BITS, dtype=np.float64) + 0.5) / (1 << TABLE_BITS)
    return (-mean * np.log1p(-u)).astype(np.float32)


def _mix(bits, lp):
    h = (bits * jnp.uint32(2654435761)
         + lp.astype(jnp.uint32) * jnp.uint32(40503)
         + jnp.uint32(12345))
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0x5BD1E995)
    return h ^ (h >> 15)


def initial_times(cfg: dict, seed: int) -> np.ndarray:
    """``f32[num_lps, start_events]``: ``lookahead + Exp(mean)`` drawn
    from ``seed``; LP-major order is the seed's seq order."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, 1 << TABLE_BITS,
                       size=(cfg["num_lps"], cfg["start_events"]))
    return np.float32(cfg["lookahead"]) + exp_table(cfg["mean"])[idx]


def program(cfg: dict, seed: int) -> SimProgram:
    """The PHOLD program with its seeded start events scheduled."""
    num_lps = cfg["num_lps"]
    la = np.float32(cfg["lookahead"])
    remote_below = int(round(cfg["remote"] * 65536))
    table = jnp.asarray(exp_table(cfg["mean"]))
    prog = SimProgram(
        "phold",
        config=Config(max_batch_len=cfg["max_batch_len"],
                      capacity=cfg["capacity"], max_emit=1),
    )

    @prog.handler("HOP", lookahead=float(la), emits=True)
    def hop(state, t, arg):
        src = arg[0].astype(jnp.int32)
        h = _mix(jax.lax.bitcast_convert_type(t, jnp.uint32), src)
        delay = la + table[(h & jnp.uint32(0xFFFF)).astype(jnp.int32)]
        g = (h ^ (h >> 16)) * jnp.uint32(0x45D9F3B)
        g = g ^ (g >> 16)
        remote = (h >> 16) < jnp.uint32(remote_below)
        dst = jnp.where(remote, (g % jnp.uint32(num_lps)).astype(jnp.int32),
                        src)
        counts = state["counts"].at[src].add(1)
        checksum = state["checksum"] * jnp.uint32(31) + h
        emit = jnp.zeros((1, 2 + ARG_WIDTH), jnp.float32)
        emit = (emit.at[0, 0].set(delay)
                    .at[0, 1].set(0.0)
                    .at[0, 2].set(dst.astype(jnp.float32)))
        return {"counts": counts, "checksum": checksum}, emit

    times = initial_times(cfg, seed)
    for lp in range(num_lps):
        for t in times[lp]:
            prog.schedule(float(t), "HOP", arg=[float(lp)])
    return prog


def initial_state(cfg: dict):
    return {
        "counts": jnp.zeros((cfg["num_lps"],), jnp.int32),
        "checksum": jnp.uint32(1),
    }


def observe(state) -> dict:
    """The host copy of the state the reference is compared with."""
    return {"counts": state["counts"], "checksum": int(state["checksum"])}
