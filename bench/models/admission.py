"""Open serving admission as the engine runs it: the benchmark's copy.

Copied from ``repro.serving.scenarios.build_open_admission_program`` so
that an edit to the scenario cannot move the yardstick.  ``ARRIVE``
comes from the external stream and emits an ``ADMIT`` 0.25 later;
``ADMIT`` takes the first free slot with a hashed decode budget, or
retries one tick later; ``TICK`` decodes every active slot once per
unit of time and keeps itself alive while work remains or can arrive.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.program import EMIT_WIDTH, Config, SimProgram

ARRIVE, ADMIT, TICK = 0.0, 1.0, 2.0


def _hash_mod(k, salt: int, mod: int):
    h = (k + jnp.int32(salt)) * jnp.int32(1103515245)
    return jnp.abs(h) % jnp.int32(mod)


def program(cfg: dict, num_requests: int) -> SimProgram:
    max_emit = cfg["max_emit"]
    max_decode = cfg["max_decode"]
    prog = SimProgram(
        "serving-admission-open",
        config=Config(max_batch_len=cfg["max_batch_len"],
                      capacity=cfg["capacity"], max_emit=max_emit),
    )

    def _blank():
        return jnp.full((max_emit, EMIT_WIDTH), -1.0, jnp.float32)

    @prog.handler("ARRIVE", lookahead=0.25, emits=True)
    def arrive(state, t, arg):
        k = state["arrivals"]
        state = dict(state, arrivals=k + 1, waiting=state["waiting"] + 1)
        emits = _blank()
        emits = emits.at[0, 0].set(0.25).at[0, 1].set(ADMIT)
        emits = emits.at[0, 2].set(k.astype(jnp.float32))
        return state, emits

    @prog.handler("ADMIT", lookahead=1.0, emits=True)
    def admit(state, t, arg):
        slots = state["slots"]
        free = slots <= 0
        any_free = jnp.any(free)
        have_wait = state["waiting"] > 0
        do = have_wait & any_free
        took = do.astype(jnp.int32)
        slot = jnp.argmax(free)
        budget = 1 + _hash_mod(state["admitted"], 977, max_decode)
        slots = jnp.where(do, slots.at[slot].set(budget), slots)
        retry = have_wait & ~any_free
        state = dict(
            state, slots=slots,
            waiting=state["waiting"] - took,
            admitted=state["admitted"] + took,
            retries=state["retries"] + retry.astype(jnp.int32),
        )
        emits = _blank()
        emits = emits.at[0, 0].set(1.0).at[0, 1].set(
            jnp.where(retry, ADMIT, -1.0))
        emits = emits.at[0, 2].set(arg[0])
        return state, emits

    @prog.handler("TICK", lookahead=1.0, emits=True)
    def tick(state, t, arg):
        slots = state["slots"]
        active = slots > 0
        slots = jnp.where(active, slots - 1, slots)
        finished = active & (slots == 0)
        state = dict(
            state, slots=slots,
            served=state["served"] + jnp.sum(finished).astype(jnp.int32),
            decoded=state["decoded"] + jnp.sum(active).astype(jnp.int32),
        )
        more = ((state["arrivals"] < num_requests)
                | (state["waiting"] > 0) | jnp.any(slots > 0))
        emits = _blank()
        emits = emits.at[0, 0].set(1.0).at[0, 1].set(
            jnp.where(more, TICK, -1.0))
        emits = emits.at[0, 2].set(0.0)
        return state, emits

    prog.schedule(1.0, "TICK")
    prog.external_entry("ARRIVE")
    return prog.freeze()


def initial_state(cfg: dict):
    return {
        "slots": jnp.zeros((cfg["num_slots"],), jnp.int32),
        "waiting": jnp.int32(0),
        "arrivals": jnp.int32(0),
        "admitted": jnp.int32(0),
        "served": jnp.int32(0),
        "decoded": jnp.int32(0),
        "retries": jnp.int32(0),
    }


def observe(state) -> dict:
    """The host copy of the state the reference is compared with."""
    out = {k: int(v) for k, v in state.items() if k != "slots"}
    out["slots"] = state["slots"]
    return out
