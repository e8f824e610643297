"""Bring-up smoke test: the device engine on a TPU, at real size.

    python chip_smoke.py [--seed N]      # phases A-C on one chip
    python chip_smoke.py --four-chips    # sharded PHOLD across 4 chips

Phase A drives the main path, ``SimProgram.build(backend="device")``
with its defaults (tiered3 queue, switch dispatch, XLA queue ops), on
PHOLD with 2^20 pending events (65,536 LPs x 16 messages): a
full-horizon run checked against the conservation law with no drops,
and a shared-horizon run checked bit-for-bit against the same program
on the host's CPU device.  Phase B builds the same program with
``queue_kernels="pallas"``, checks that the kernels went through Mosaic
(``tpu_custom_call``), and that it matches Phase A bit-for-bit.  Phase C
streams 100k seeded Poisson requests of the open admission scenario into
a capacity-32k queue with ``overflow="spill"`` and checks the result
against the pre-seeded closed run.

``--four-chips`` runs only Phase A's PHOLD on ``shards=4,
placement="devices"`` and compares it with the single queue on one
chip of the same process.

Earlier lines report each phase (informational, not claims).  The last
line is ``{"ok": true, "device": {...}}``.  Any failure, or a process
in which JAX finds no TPU, exits nonzero without that line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from examples import phold  # noqa: E402

# Phase A/B: PHOLD at a pending-set size PDES deployments run.
PHOLD_LPS = 65_536
PHOLD_MSGS = 16
PHOLD_BATCH = 16
PHOLD_UNTIL = 8.0          # ~1.5M committed events at 2^20 pending
SHARED_BATCHES = 10_000    # horizon the CPU reference also runs
FOUR_CHIP_UNTIL = 2.0      # ~380k committed events per engine
# Phase C: the streaming benchmark's trace replay, cut from 1M to 100k
# requests so that the whole script, cold compiles included, stays well
# inside 20 minutes on one v5e.
STREAM_REQUESTS = 100_000
STREAM_CAPACITY = 32_768
STREAM_SLOTS = 1024
STREAM_RATE = 4.0
STREAM_BLOCK = 4096


def _log(step: str, **info) -> None:
    """Report a finished step before its checks run, so a failing
    check still leaves its numbers behind."""
    print(f"phase {step}: {json.dumps(info, default=str)}", flush=True)


def _timed(fn):
    t = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _same_run(a, b, what: str) -> None:
    """Bit-for-bit equality of two PHOLD RunResults."""
    for key in ("events", "batches", "dropped"):
        assert getattr(a, key) == getattr(b, key), (
            what, key, getattr(a, key), getattr(b, key))
    assert np.float32(a.final_time) == np.float32(b.final_time), (
        what, "final_time", a.final_time, b.final_time)
    for key in ("counts", "checksum"):
        np.testing.assert_array_equal(
            np.asarray(a.state[key]), np.asarray(b.state[key]),
            err_msg=f"{what}: state[{key!r}]")


# ---------------------------------------------------------------------------
# Phase A: PHOLD through the default device path
# ---------------------------------------------------------------------------

def phold_program(num_lps: int, msgs_per_lp: int, seed: int):
    population = num_lps * msgs_per_lp
    return phold.build_program(
        num_lps=num_lps, t_stop=float("inf"), max_batch_len=PHOLD_BATCH,
        capacity=population + population // 16,
        msgs_per_lp=msgs_per_lp, seed=seed,
    )


def phase_a(*, num_lps=PHOLD_LPS, msgs_per_lp=PHOLD_MSGS, seed=0,
            until=PHOLD_UNTIL, shared_batches=SHARED_BATCHES,
            ref_device=None):
    """Default device path at real size.  Returns the info dict and the
    shared-horizon result Phase B must reproduce."""
    ref_device = ref_device or jax.devices("cpu")[0]
    prog, build_s = _timed(
        lambda: phold_program(num_lps, msgs_per_lp, seed))
    population = len(prog.scheduled_events())
    sim = prog.build(backend="device")
    _, warm_s = _timed(lambda: sim.run(
        phold.initial_state(num_lps), max_batches=0))
    info = dict(population=population, capacity=sim.engine.capacity,
                build_s=build_s, first_call_s=warm_s)
    _log("A/compile", **info)

    full, full_s = _timed(lambda: sim.run(
        phold.initial_state(num_lps), until=until))
    info.update(full_events=full.events, full_batches=full.batches,
                full_final_time=full.final_time, full_run_s=full_s,
                peak_bytes_in_use=_peak_bytes())
    _log("A/full", **info)
    assert full.dropped == 0, full.dropped
    assert population + full.emitted == (
        full.events + full.pending + full.dropped), full.stats()
    assert full.events > 0
    assert int(np.asarray(full.state["counts"]).sum()) == full.events

    shared, shared_s = _timed(lambda: sim.run(
        phold.initial_state(num_lps), max_batches=shared_batches))
    with jax.default_device(ref_device):
        ref_sim = phold_program(num_lps, msgs_per_lp, seed).build(
            backend="device")
        ref, ref_s = _timed(lambda: ref_sim.run(
            phold.initial_state(num_lps), max_batches=shared_batches))
    info.update(shared_events=shared.events, shared_batches=shared.batches,
                shared_run_s=shared_s, reference_device=str(ref_device),
                reference_s=ref_s)
    _log("A/shared", **info)
    _same_run(shared, ref, f"phase A vs reference on {ref_device}")
    return info, shared


# ---------------------------------------------------------------------------
# Phase B: the Pallas front-tier kernels
# ---------------------------------------------------------------------------

def kernel_differential(*, capacity=4096, steps=24, k=PHOLD_BATCH,
                        rows=PHOLD_BATCH, seed=0):
    """Random fill/extract streams through the XLA and Pallas queue ops
    at engine widths; every queue field must agree after every op."""
    import jax.numpy as jnp

    from repro.core.events import ARG_WIDTH
    from repro.core.queue import (
        tiered3_queue_extract,
        tiered3_queue_fill_rows,
        tiered3_queue_init,
    )

    def step(kernels):
        def fill_extract(q, rows_in, cap):
            q = tiered3_queue_fill_rows(q, rows_in, kernels=kernels)
            return tiered3_queue_extract(q, k, la, cap, kernels=kernels)
        return jax.jit(fill_extract)

    rng = np.random.default_rng(seed)
    la = jnp.asarray([0.5, 1.0, 0.25], jnp.float32)
    run_x, run_p = step("xla"), step("pallas")
    qx = qp = tiered3_queue_init(capacity, arg_width=ARG_WIDTH)
    for i in range(steps):
        r = np.zeros((rows, 2 + ARG_WIDTH), np.float32)
        r[:, 0] = rng.integers(0, 64, rows) * 0.25 + i
        r[:, 1] = rng.integers(-1, 3, rows)
        r[:, 2:] = rng.standard_normal((rows, ARG_WIDTH))
        cap = jnp.float32(i + 8.0)
        qx, *out_x = run_x(qx, jnp.asarray(r), cap)
        qp, *out_p = run_p(qp, jnp.asarray(r), cap)
        for a, b in zip(jax.tree.leaves((qx, out_x)),
                        jax.tree.leaves((qp, out_p))):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"step {i}")
    return steps


def phase_b(reference, *, num_lps=PHOLD_LPS, msgs_per_lp=PHOLD_MSGS,
            seed=0, shared_batches=SHARED_BATCHES):
    """Same program with ``queue_kernels="pallas"`` over Phase A's
    shared horizon."""
    from repro.kernels import interpret_mode

    steps, diff_s = _timed(kernel_differential)
    info = dict(kernel_diff_steps=steps, kernel_diff_s=diff_s)
    _log("B/kernels", **info)
    sim = phold_program(num_lps, msgs_per_lp, seed).build(
        backend="device", queue_kernels="pallas")
    mosaic = "tpu_custom_call" in sim.engine.lower_run(
        jax.eval_shape(lambda: phold.initial_state(num_lps)),
        jax.eval_shape(lambda: sim.engine.initial_queue(())),
    ).as_text()
    assert mosaic or interpret_mode(), "pallas kernels missing from HLO"
    _, warm_s = _timed(lambda: sim.run(
        phold.initial_state(num_lps), max_batches=0))
    res, run_s = _timed(lambda: sim.run(
        phold.initial_state(num_lps), max_batches=shared_batches))
    info.update(tpu_custom_call=mosaic, events=res.events,
                batches=res.batches, first_call_s=warm_s, run_s=run_s,
                peak_bytes_in_use=_peak_bytes())
    _log("B/shared", **info)
    _same_run(res, reference, "phase B (pallas) vs phase A")
    return info


# ---------------------------------------------------------------------------
# Phase C: an open run, streamed through the spill queue
# ---------------------------------------------------------------------------

def phase_c(*, n_requests=STREAM_REQUESTS, capacity=STREAM_CAPACITY,
            slots=STREAM_SLOTS, seed=0, block_size=STREAM_BLOCK):
    """Streamed ``run(arrivals=...)`` against the pre-seeded closed
    run of the same trace."""
    from repro.core.program import Config
    from repro.serving.scenarios import (
        build_open_admission_program,
        initial_state,
    )
    from repro.stream import PoissonSource, source_events

    def program(cap):
        return build_open_admission_program(
            num_slots=slots, num_requests=n_requests, max_decode=6,
            config=Config(max_batch_len=3, capacity=cap, max_emit=2))

    source = PoissonSource(STREAM_RATE, n_requests, seed=seed, grid=0.25,
                           type_id=0, block_size=block_size)
    state0 = initial_state(slots)
    sim = program(capacity).build(backend="device", overflow="spill")
    streamed, stream_s = _timed(lambda: sim.run(state0, arrivals=source))
    info = dict(requests=n_requests, capacity=capacity,
                events=streamed.events, streamed_batches=streamed.batches,
                streamed_s=stream_s, peak_bytes_in_use=_peak_bytes())
    _log("C/streamed", **info)
    assert streamed.ingested == n_requests, streamed.ingested

    events = [(1.0, "TICK")] + [
        (t, ty, list(a)) for (t, ty, a) in source_events(source)]
    closed_sim = program(n_requests + 4096).build(backend="device")
    closed, closed_s = _timed(lambda: closed_sim.run(state0, events=events))
    info.update(closed_batches=closed.batches, closed_s=closed_s,
                peak_bytes_in_use=_peak_bytes())
    _log("C/closed", **info)
    assert streamed.events == closed.events, (streamed.events, closed.events)
    assert streamed.dropped == closed.dropped == 0
    assert np.float32(streamed.final_time) == np.float32(closed.final_time)
    for key, value in closed.state.items():
        np.testing.assert_array_equal(
            np.asarray(streamed.state[key]), np.asarray(value),
            err_msg=f"phase C: state[{key!r}]")
    return info


# ---------------------------------------------------------------------------
# Four chips: sharded placement against the single queue
# ---------------------------------------------------------------------------

def four_chips(*, num_lps=PHOLD_LPS, msgs_per_lp=PHOLD_MSGS, seed=0,
               until=FOUR_CHIP_UNTIL, shards=4):
    """PHOLD on ``shards`` devices (``placement="devices"``) against the
    single tiered3 queue on the process's default device."""
    single_sim = phold_program(num_lps, msgs_per_lp, seed).build(
        backend="device")
    single, single_s = _timed(lambda: single_sim.run(
        phold.initial_state(num_lps), until=until))
    info = dict(events=single.events, batches=single.batches,
                final_time=single.final_time, single_s=single_s)
    _log("4-chip/single", **info)
    sim = phold_program(num_lps, msgs_per_lp, seed).build(
        backend="device", shards=shards, placement="devices")
    queue = sim.engine.initial_queue(sim.program.scheduled_events())
    placed = {d for leaf in jax.tree.leaves(queue.q)
              for d in leaf.sharding.device_set}
    info.update(shards=shards, queue_devices=sorted(str(d) for d in placed))
    assert len(placed) == shards, f"stacked queue on {placed}"
    res, run_s = _timed(lambda: sim.run(
        phold.initial_state(num_lps), until=until))
    info.update(sharded_events=res.events, sharded_batches=res.batches,
                sharded_s=run_s)
    _log("4-chip/sharded", **info)
    _same_run(res, single, f"{shards} shards on devices vs single queue")
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard PHOLD on four chips")
    args = ap.parse_args(argv)

    start = time.perf_counter()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
        return 1
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    print(f"device: {dev.device_kind} x{len(devices)}", flush=True)
    if args.four_chips:
        assert len(devices) >= 4, f"--four-chips needs 4 chips: {devices}"
        four_chips(seed=args.seed)
    else:
        _, shared = phase_a(seed=args.seed)
        phase_b(shared, seed=args.seed)
        phase_c(seed=args.seed)
    _log("done", total_s=time.perf_counter() - start)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
