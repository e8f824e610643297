"""The paper's §IV.A wireless-broadcast sketch, made concrete.

"Suppose a node in a simulated network periodically broadcasts messages
to nearby receivers.  The successful reception depends on whether the
receiver is in a power-saving state.  If none of the nearby nodes is
ready to receive, the computations involved in the creation of the
message could be avoided entirely."

Events:
* SleepAll     — every receiver enters power saving (awake = 0)
* WakeAll      — every receiver wakes (awake = 1)
* Broadcast    — sender builds an expensive message (a long mixing
                 loop) and delivers it to awake receivers.

In the batch [SleepAll, Broadcast], the delivery mask is all-zero — XLA's
cross-event DCE removes the message-construction loop, exactly the
paper's motivating scenario.  Verified on the optimized HLO below.

The model is defined ONCE on a :class:`repro.api.SimProgram` and then
compiled to the host scheduler and to the on-device engine in both
queue modes — same definition, every runtime, identical inboxes.

    PYTHONPATH=src python examples/wireless_des.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import Config, SimProgram
from repro.compile_cache import use_compile_cache
from repro.core import compose_word_fn

N_RECEIVERS = 4
MSG_WORK = 100_000
SLEEP, WAKE, BCAST = 0, 1, 2  # registration-order type ids


def build_program() -> SimProgram:
    prog = SimProgram(
        "wireless",
        config=Config(max_batch_len=2, capacity=64),
    )

    @prog.handler("SleepAll")
    def sleep_all(state, t, arg):
        return {**state, "awake": jnp.zeros_like(state["awake"])}

    @prog.handler("WakeAll")
    def wake_all(state, t, arg):
        return {**state, "awake": jnp.ones_like(state["awake"])}

    @prog.handler("Broadcast")
    def broadcast(state, t, arg):
        # expensive message construction (mixing loop)
        msg = jax.lax.fori_loop(
            0, MSG_WORK,
            lambda i, m: m * jnp.uint32(1664525) + jnp.uint32(1013904223),
            jnp.uint32(12345))
        # delivery gated by receiver power state
        delivered = state["inbox"] + state["awake"] * msg
        return {**state, "inbox": delivered.astype(jnp.uint32)}

    # day/night duty cycle with periodic broadcasts
    for day in range(8):
        base = day * 10.0
        prog.schedule(base + 0.0, "SleepAll")
        prog.schedule(base + 1.0, "Broadcast")
        prog.schedule(base + 2.0, "Broadcast")
        prog.schedule(base + 5.0, "WakeAll")
        prog.schedule(base + 6.0, "Broadcast")
    return prog


def initial_state():
    return {
        "awake": jnp.ones((N_RECEIVERS,), jnp.uint32),
        "inbox": jnp.zeros((N_RECEIVERS,), jnp.uint32),
    }


def make_program():
    """Analyzer/CLI target: the paper §IV.A wireless scenario with its
    example state declared."""
    return build_program().example_state(initial_state())


def main():
    use_compile_cache()
    prog = build_program()

    # cross-event DCE check: [SleepAll, Broadcast, WakeAll] -> no one can
    # receive, so the message-construction loop must disappear.  The
    # composed word programs come from the program's host registry.
    state_spec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), initial_state())
    t_spec = [jax.ShapeDtypeStruct((), jnp.float32)] * 3

    reg = prog.host_registry()
    dead = compose_word_fn(reg, [SLEEP, BCAST, WAKE])
    live = compose_word_fn(reg, [WAKE, BCAST, SLEEP])
    hlo_dead = jax.jit(dead).lower(state_spec, t_spec,
                                   [None] * 3).compile().as_text()
    hlo_live = jax.jit(live).lower(state_spec, t_spec,
                                   [None] * 3).compile().as_text()
    print("message loop removed when all receivers sleep:",
          " while(" not in hlo_dead)
    print("message loop present when receivers awake:   ",
          " while(" in hlo_live)

    # host runtime
    host = prog.build(backend="host", scheduler="conservative")
    res = host.run(initial_state())
    print(f"host run: batches executed: {res.batches} "
          f"(mean len {res.mean_batch_length:.1f}); "
          f"final inbox: {np.asarray(res.state['inbox'])}")

    # SAME definition compiled to ONE on-device program: queue, window
    # selection, and dispatch all run inside a single lax.while_loop —
    # zero host round-trips during the run.  The default pending-event
    # set is the two-tier queue (DESIGN.md §4), so the engine can be
    # provisioned with deep capacity headroom at no per-batch cost.
    # CompiledSim.run rebuilds the donated device queue each call, so
    # the handle is freely re-runnable.
    for queue_mode, capacity in (("tiered", 4096), ("flat", 64)):
        dev = prog.build(backend="device", queue_mode=queue_mode,
                         capacity=capacity)
        dres = dev.run(initial_state())
        same = bool((np.asarray(dres.state["inbox"])
                     == np.asarray(res.state["inbox"])).all())
        print(f"on-device engine [{queue_mode:6s} queue, "
              f"capacity {capacity:4d}]: batches={dres.batches} "
              f"events={dres.events} "
              f"dropped={dres.dropped}; matches host run: {same}")


if __name__ == "__main__":
    main()
