"""A tandem M/M/c queueing network, written once, every runtime.

``K`` stations in series, each with ``c`` servers.  Customers enter at
station 0 (a self-scheduling arrival source), receive service (queueing
when all ``c`` servers are busy), and are routed to the next station on
departure.  A third, *entity-parallel* event type — TALLY — samples the
per-station queue length on a fixed grid: all K tallies share one
timestamp, so the extracted window is a single-type run and the device
engine dispatches it as ONE ``vmap`` over the stations
(``@prog.entity_handler``) instead of a sequential switch branch.

Like examples/phold.py, service/interarrival times are counter-based
hashes on the 0.25 time grid, so every backend — host conservative /
speculative / unbatched and device tiered / flat / reference — produces
bit-identical final state; the example asserts it.

    PYTHONPATH=src python examples/mmc_network.py [--stations 4] [--tiny]
"""

import argparse

import jax.numpy as jnp
import numpy as np

from repro.api import ARG_WIDTH, Config, SimProgram
from repro.compile_cache import use_compile_cache

ARRIVE, DEPART, TALLY = 0, 1, 2  # registration-order type ids
C_SERVERS = 2

BACKENDS = {
    "host/conservative": dict(backend="host", scheduler="conservative"),
    "host/speculative": dict(backend="host", scheduler="speculative"),
    "host/unbatched": dict(backend="host", scheduler="unbatched"),
    "device/tiered": dict(backend="device", queue_mode="tiered"),
    "device/flat": dict(backend="device", queue_mode="flat"),
    "device/reference": dict(backend="device", queue_mode="reference"),
}


def _mix(t, station, salt: int):
    """Counter-based hash of (time, station, stream): exact on the 0.25
    time grid, identical across backends."""
    t4 = (t * 4.0).astype(jnp.uint32)
    h = (t4 * jnp.uint32(2654435761)
         + station.astype(jnp.uint32) * jnp.uint32(40503)
         + jnp.uint32(salt) * jnp.uint32(97))
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0x5BD1E995)
    return h ^ (h >> 15)


def _delay(h, lo: float = 0.5, steps: int = 8):
    """Grid-exact pseudo-exponential delay in {lo, lo+0.25, ...}."""
    return lo + (h % steps).astype(jnp.float32) * 0.25


def _row(cond, delay, type_id, a0, a1=None):
    """One portable emit row (delay, type, arg...); ν when cond is
    False."""
    zero = jnp.float32(0.0)
    ty = jnp.where(cond, jnp.float32(type_id), jnp.float32(-1.0))
    a1 = zero if a1 is None else a1
    pad = [zero] * (ARG_WIDTH - 2)
    return jnp.stack([delay.astype(jnp.float32), ty,
                      a0.astype(jnp.float32), a1] + pad)


def build_program(num_stations: int = 4, t_open: float = 30.0,
                  tally_every: float = 5.0, max_batch_len: int | None = None,
                  capacity: int = 512) -> SimProgram:
    """The network model.  ``max_batch_len`` defaults to the station
    count so a tally grid point fills exactly one vmapped window."""
    K = num_stations
    max_batch_len = K if max_batch_len is None else max_batch_len
    prog = SimProgram(
        "mmc_network",
        config=Config(max_batch_len=max_batch_len, capacity=capacity,
                      max_emit=2),
    )

    @prog.handler("ARRIVE", lookahead=0.5, emits=True)
    def arrive(state, t, arg):
        s = arg[0].astype(jnp.int32)
        is_source = arg[1] > 0.5  # the self-scheduling external stream
        service = _delay(_mix(t, s, 17))
        free = state["busy"][s] < C_SERVERS
        state = {
            **state,
            "busy": state["busy"].at[s].add(jnp.where(free, 1, 0)),
            "qlen": state["qlen"].at[s].add(jnp.where(free, 0, 1)),
            "arrived": state["arrived"].at[s].add(1),
        }
        next_gap = _delay(_mix(t, s, 23), lo=0.5, steps=6)
        emits = jnp.stack([
            # free server: begin service now, schedule the departure
            _row(free, service, DEPART, s.astype(jnp.float32)),
            # external source keeps itself alive while the doors are open
            _row(is_source & (t < t_open), next_gap, ARRIVE,
                 jnp.float32(0.0), jnp.float32(1.0)),
        ])
        return state, emits

    @prog.handler("DEPART", lookahead=0.5, emits=True)
    def depart(state, t, arg):
        s = arg[0].astype(jnp.int32)
        service = _delay(_mix(t, s, 29))
        waiting = state["qlen"][s] > 0
        state = {
            **state,
            "qlen": state["qlen"].at[s].add(jnp.where(waiting, -1, 0)),
            "busy": state["busy"].at[s].add(jnp.where(waiting, 0, -1)),
            "served": state["served"].at[s].add(1),
        }
        route = s < K - 1
        emits = jnp.stack([
            # a waiting customer takes the freed server immediately
            _row(waiting, service, DEPART, s.astype(jnp.float32)),
            # the finished customer hops to the next station in series
            _row(route, jnp.float32(0.5), ARRIVE,
                 (s + 1).astype(jnp.float32)),
        ])
        return state, emits

    @prog.entity_handler("TALLY", lookahead=1.0)
    def tally(entity_state, t, arg):
        # Entity-local: `entity_state` is one station's slice of every
        # state leaf.  Integrates queue length over the sample grid.
        return {
            **entity_state,
            "area": entity_state["area"] + entity_state["qlen"],
            "samples": entity_state["samples"] + 1,
        }

    prog.schedule(0.0, "ARRIVE", arg=[0.0, 1.0])
    g = tally_every
    while g < t_open + 10.0:
        for s in range(K):
            prog.schedule(g, "TALLY", arg=[float(s)])
        g += tally_every
    return prog


def initial_state(num_stations: int):
    z = jnp.zeros((num_stations,), jnp.int32)
    return {"qlen": z, "busy": z, "served": z, "arrived": z,
            "area": z, "samples": z}


def make_program():
    """Analyzer/CLI target: smoke-size M/M/c network with its example
    state declared."""
    prog = build_program(num_stations=4, t_open=15.0)
    return prog.example_state(initial_state(4))


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--stations", type=int, default=4)
    ap.add_argument("--t-open", type=float, default=30.0)
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (3 stations, short horizon)")
    args = ap.parse_args()
    K = 3 if args.tiny else args.stations
    t_open = 10.0 if args.tiny else args.t_open

    results = {}
    for label, build_kw in BACKENDS.items():
        prog = build_program(num_stations=K, t_open=t_open)
        res = prog.build(**build_kw).run(initial_state(K))
        results[label] = res
        print(f"{label:20s} events={res.events:5d} batches={res.batches:5d} "
              f"(mean len {res.mean_batch_length:4.2f}) "
              f"rollbacks={res.rollbacks:3d} served={np.asarray(res.state['served'])}")

    base = results["host/unbatched"]
    for label, res in results.items():
        for leaf in ("qlen", "busy", "served", "arrived", "area", "samples"):
            assert (np.asarray(res.state[leaf])
                    == np.asarray(base.state[leaf])).all(), (label, leaf)
        assert res.events == base.events and res.dropped == base.dropped, label

    st = base.state
    # conservation: everyone who arrived is served, queued, or in service
    assert (np.asarray(st["arrived"])
            == np.asarray(st["served"]) + np.asarray(st["qlen"])
            + np.asarray(st["busy"])).all()
    mean_q = np.asarray(st["area"]) / np.maximum(np.asarray(st["samples"]), 1)
    print(f"\nall {len(results)} runtimes agree bit-for-bit; "
          f"mean queue length per station: {np.round(mean_q, 2)}")


if __name__ == "__main__":
    main()
