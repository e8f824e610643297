"""Quickstart: the paper's Increment/Set model in 40 lines.

Shows the whole method end to end with the `repro.api` surface: define
the model once on a SimProgram, observe the cross-event optimization
(XLA removing the dead Increment loop) on a composed batch, then compile
THE SAME definition to the batched lookahead-window scheduler and to the
one-by-one baseline and measure the speedup.

    PYTHONPATH=src python examples/quickstart.py
"""

import time

import jax
import numpy as np

from repro import poc
from repro.compile_cache import use_compile_cache
from repro.core import compose_word_fn

ITERS = 300_000
EVENTS = 200


def main():
    use_compile_cache()
    # 1. The event alphabet: Increment (heavy loop) and Set (constant),
    #    declared once on a SimProgram.
    prog = poc.build_program(iters=ITERS)

    # 2. Compile-time cross-event optimization, observed directly:
    import jax.numpy as jnp
    batch = compose_word_fn(prog.host_registry(), [poc.INCREMENT, poc.SET])
    hlo = jax.jit(batch).lower(
        jax.ShapeDtypeStruct((), jnp.uint32),
        [jax.ShapeDtypeStruct((), jnp.float32)] * 2,
        [None, None]).compile().as_text()
    print("batch [Increment, Set]: increment loop removed by XLA:",
          " while(" not in hlo)

    # 3. Run a simulation: one event per time step, 50% Set.
    rng = np.random.default_rng(0)
    types = [int(x) for x in (rng.random(EVENTS) < 0.5)]
    for t, ty in enumerate(types):
        prog.schedule(float(t), ("Increment", "Set")[ty])

    # Two runtimes from the same definition; CompiledSim handles are
    # re-runnable, so the second run of each is warm (compiled).
    batched = prog.build(backend="host", scheduler="conservative")
    unbatched = prog.build(backend="host", scheduler="unbatched")

    def timed(sim):
        t0 = time.perf_counter()
        res = sim.run(poc.initial_state())
        jax.block_until_ready(res.state)
        return time.perf_counter() - t0, res

    timed(batched)          # warm-up (composes + compiles)
    timed(unbatched)
    t_batched, res_b = timed(batched)
    t_single, res_u = timed(unbatched)
    assert int(res_b.state) == int(res_u.state) \
        == poc.reference_final_sum(types, ITERS)
    print(f"events={EVENTS}  batches={res_b.batches} "
          f"(mean length {res_b.mean_batch_length:.1f})")
    print(f"one-by-one: {t_single*1e3:.1f} ms   "
          f"batched: {t_batched*1e3:.1f} ms   "
          f"speedup: {t_single/t_batched:.2f}x "
          f"(analytic bound {poc.s_max(4, 0.5):.2f}x)")


if __name__ == "__main__":
    main()
