"""PHOLD, written once, runnable on every runtime.

PHOLD is the standard synthetic PDES benchmark (Fujimoto, 1990): a
constant population of messages hops between logical processes; each
executed hop schedules exactly one future hop at a pseudo-random LP
with a pseudo-random delay.  It stresses the part the PoC model leaves
out — a hot emit/insert path with data-dependent routing.

The model is defined ONCE on a :class:`repro.api.SimProgram` and then
compiled to every runtime (host conservative / speculative /
unbatched; device tiered3 / tiered / flat / reference queues; add
``--shards N`` for the sharded engine).  Every run must
produce the same final state bit-for-bit, including the
order-sensitive ``checksum`` — the randomness is a counter-based hash
of ``(time, lp)`` and every delay is a multiple of 0.5, so f32 device
arithmetic and the host heap agree exactly.

    PYTHONPATH=src python examples/phold.py [--lps 8] [--t-stop 40] [--tiny]
                                            [--shards N]

``--shards N`` adds the sharded device engine (N per-shard tiered3
queues under the lookahead-synchronized super-step, DESIGN.md §5.1) to
the matrix — LPs route to shards by their index, and the run must stay
bit-identical to every single-queue backend.
"""

import argparse

import jax.numpy as jnp
import numpy as np

from repro.api import ARG_WIDTH, Config, SimProgram
from repro.compile_cache import use_compile_cache

HOP = 0  # single-type alphabet: registration order id

BACKENDS = {
    "host/conservative": dict(backend="host", scheduler="conservative"),
    "host/speculative": dict(backend="host", scheduler="speculative"),
    "host/unbatched": dict(backend="host", scheduler="unbatched"),
    "device/tiered3": dict(backend="device", queue_mode="tiered3"),
    "device/tiered": dict(backend="device", queue_mode="tiered"),
    "device/flat": dict(backend="device", queue_mode="flat"),
    "device/reference": dict(backend="device", queue_mode="reference"),
}


def _mix(t, src):
    """Counter-based hash of (time, lp): deterministic 'randomness'
    that is identical on every backend.  Times stay on the 0.5 grid,
    so ``2t`` is an exact integer in f32."""
    t2 = (t * 2.0).astype(jnp.uint32)
    h = (t2 * jnp.uint32(2654435761)
         + src.astype(jnp.uint32) * jnp.uint32(40503)
         + jnp.uint32(12345))
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0x5BD1E995)
    return h ^ (h >> 15)


def build_program(num_lps: int = 8, t_stop: float = 40.0,
                  max_batch_len: int = 4, capacity: int = 256,
                  msgs_per_lp: int = 1, seed: int | None = None
                  ) -> SimProgram:
    """The PHOLD model: one emitting HOP type.

    Without ``seed`` the population is one hop per LP at ``0.5 * lp``.
    With it, each LP starts ``msgs_per_lp`` hops at times drawn from
    the 0.5 grid in ``[0, 8)`` — PHOLD's closed population, which every
    executed hop keeps constant until ``t_stop``.
    """
    prog = SimProgram(
        "phold",
        config=Config(max_batch_len=max_batch_len, capacity=capacity,
                      max_emit=1),
    )

    @prog.handler("HOP", lookahead=1.0, emits=True)
    def hop(state, t, arg):
        src = arg[0].astype(jnp.int32)
        h = _mix(t, src)
        # delay in {1.0, 1.5, ..., 4.5} >= the declared lookahead;
        # destination is any OTHER lp — both pure functions of (t, src).
        delay = 1.0 + (h % 8).astype(jnp.float32) * 0.5
        dst = (src + 1 + ((h // 8) % (num_lps - 1)).astype(jnp.int32)) \
            % num_lps
        counts = state["counts"].at[src].add(1)
        checksum = state["checksum"] * jnp.uint32(31) + h
        emit = jnp.zeros((1, 2 + ARG_WIDTH), jnp.float32)
        emit = (emit.at[0, 0].set(delay)
                    .at[0, 1].set(jnp.where(t < t_stop, 0.0, -1.0))
                    .at[0, 2].set(dst.astype(jnp.float32)))
        return {"counts": counts, "checksum": checksum}, emit

    if seed is None:
        for lp in range(num_lps):
            prog.schedule(0.5 * lp, "HOP", arg=[float(lp)])
        return prog
    rng = np.random.default_rng(seed)
    times = 0.5 * rng.integers(0, 16, size=(num_lps, msgs_per_lp))
    for lp in range(num_lps):
        for t in times[lp]:
            prog.schedule(float(t), "HOP", arg=[float(lp)])
    return prog


def initial_state(num_lps: int):
    return {
        "counts": jnp.zeros((num_lps,), jnp.int32),
        "checksum": jnp.uint32(1),
    }


def make_program():
    """Analyzer/CLI target: smoke-size PHOLD with its example state
    declared (``python -m repro.analysis examples.phold:make_program``).
    """
    prog = build_program(num_lps=8, t_stop=20.0)
    return prog.example_state(initial_state(8))


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--lps", type=int, default=8)
    ap.add_argument("--t-stop", type=float, default=40.0)
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (4 LPs, short horizon)")
    ap.add_argument("--shards", type=int, default=None,
                    help="also run the sharded device engine with this "
                         "many per-shard queues (bit-parity asserted)")
    args = ap.parse_args()
    num_lps = 4 if args.tiny else args.lps
    t_stop = 8.0 if args.tiny else args.t_stop
    backends = dict(BACKENDS)
    if args.shards:
        backends[f"device/{args.shards}shard"] = dict(
            backend="device", shards=args.shards)

    results = {}
    for label, build_kw in backends.items():
        prog = build_program(num_lps=num_lps, t_stop=t_stop)
        sim = prog.build(**build_kw)
        res = sim.run(initial_state(num_lps))
        results[label] = res
        print(f"{label:20s} events={res.events:5d} batches={res.batches:5d} "
              f"(mean len {res.mean_batch_length:4.2f}) "
              f"rollbacks={res.rollbacks:3d} dropped={res.dropped} "
              f"checksum={int(res.state['checksum']):>10d}")

    base = results["host/unbatched"]
    for label, res in results.items():
        assert int(res.state["checksum"]) == int(base.state["checksum"]), label
        assert (np.asarray(res.state["counts"])
                == np.asarray(base.state["counts"])).all(), label
        assert res.events == base.events and res.dropped == base.dropped, label
    print(f"\nall {len(results)} runtimes agree bit-for-bit: "
          f"counts={np.asarray(base.state['counts'])}")


if __name__ == "__main__":
    main()
