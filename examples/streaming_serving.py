"""Open-system serving: stream a request trace into the admission model.

The closed scenarios (examples/serve_lm.py, the admission program) bake
their arrival process into the model.  This example runs the OPEN
variant (DESIGN.md §10): requests come from a host-side arrival stream
— a synthetic Poisson source or an on-disk trace from
``scripts/gen_trace.py`` — fed block-by-block into the running device
engine with double-buffered host→device staging, while the admission
fence keeps execution bit-identical to pre-seeding the whole trace.

The example is the equivalence proof in miniature:

1. stream the trace:  ``sim.run(state0, arrivals=source)``
2. pre-seed the same trace and run the closed system
3. assert final state / events / final_time are bit-equal
4. report sustained ingest throughput (requests per wall-second)

    PYTHONPATH=src python examples/streaming_serving.py [--tiny]
        [--shards N] [--requests N] [--trace PATH] [--spill]
"""

import argparse
import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.program import Config
from repro.serving.scenarios import (
    build_open_admission_program,
    initial_state,
)
from repro.stream import PoissonSource, TraceReader, source_events


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="small sizes for CI smoke")
    ap.add_argument("--requests", type=int, default=None,
                    help="trace length (default 400, or 48 with --tiny)")
    ap.add_argument("--shards", type=int, default=0,
                    help="run the sharded device engine with N shards")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--trace", default=None,
                    help="replay an on-disk trace (scripts/gen_trace.py) "
                         "instead of the synthetic source; must be "
                         "grid=0.25, type 0")
    ap.add_argument("--spill", action="store_true",
                    help="stream through a queue smaller than the trace "
                         "(overflow='spill' parks the excess host-side)")
    args = ap.parse_args()

    if args.trace is not None:
        source = TraceReader(args.trace)
        n_req = len(source)
    else:
        n_req = args.requests or (48 if args.tiny else 400)
        source = PoissonSource(args.rate, n_req, seed=7, grid=0.25,
                               type_id=0, block_size=64)

    # without --spill the device queue must hold the worst-case backlog
    # (every request waiting on an ADMIT retry at once); --spill shows
    # the bounded-memory shape instead, parking the excess host-side
    capacity = 48 if args.spill else max(1024, n_req + 64)
    cfg = Config(max_batch_len=3, capacity=capacity, max_emit=2)

    def build():
        return build_open_admission_program(
            num_slots=args.slots, num_requests=n_req, config=cfg)

    kw = dict(backend="device")
    if args.shards:
        kw["shards"] = args.shards
    if args.spill:
        kw["overflow"] = "spill"

    sim = build().build(**kw)
    state0 = initial_state(args.slots)
    sim.run(state0, arrivals=source)  # warm the jit caches
    source.seek(0)
    wall = time.perf_counter()
    streamed = sim.run(state0, arrivals=source)
    wall = time.perf_counter() - wall
    rps = streamed.ingested / wall
    print(f"streamed : {streamed.ingested} requests ingested, "
          f"{streamed.events} events, served="
          f"{int(streamed.state['served'])}, "
          f"final_time={streamed.final_time:.2f}")
    print(f"           {wall * 1e3:.1f} ms wall -> {rps:,.0f} sustained RPS")

    # closed-system reference: seeds first, then the trace (the seq
    # discipline the streamed run reserves for)
    closed_cfg = Config(max_batch_len=3, capacity=max(1024, n_req + 64),
                        max_emit=2)

    def build_closed():
        return build_open_admission_program(
            num_slots=args.slots, num_requests=n_req, config=closed_cfg)

    events = [(1.0, "TICK")] + [
        (t, ty, list(arg)) for (t, ty, arg) in source_events(source)
    ]
    closed = build_closed().build(backend="device").run(
        state0, events=events)
    print(f"closed   : {closed.events} events, "
          f"served={int(closed.state['served'])}, "
          f"final_time={closed.final_time:.2f}")

    for k, v in closed.state.items():
        np.testing.assert_array_equal(
            np.asarray(streamed.state[k]), np.asarray(v), err_msg=k)
    assert streamed.events == closed.events
    assert streamed.dropped == closed.dropped == 0
    assert np.float32(streamed.final_time) == np.float32(closed.final_time)
    print("equivalence: streamed run is bit-identical to pre-seeding "
          "the trace")


if __name__ == "__main__":
    main()
