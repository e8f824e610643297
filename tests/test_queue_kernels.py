"""Differential suites for the Pallas front-tier queue kernels.

The ``queue_kernels="pallas"`` paths must be BIT-IDENTICAL to the XLA
tiered3 paths (which the reference-queue suites already pin), so every
assertion here is ``assert_array_equal`` on every queue field — no
tolerances.  Kernels run in interpret mode on CPU (the repo-wide
Pallas idiom, see repro/kernels/ops.py), so these are exact semantics
tests of the kernel bodies; the fast cases run in the CI fast lane,
the full-capacity sweeps are ``slow``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.queue import (
    tiered3_queue_extract,
    tiered3_queue_fill_rows,
    tiered3_queue_fill_rows_tagged,
    tiered3_queue_init,
    tiered3_queue_peek_front,
    window_prefix_mask,
)
from repro.kernels.queue_front import front_merge, window_extract

from repro import poc
from repro.core.program import Config

# The queue ops jitted once per shape: called eagerly, every lax.cond
# inside them is traced and compiled again on every step.
_fill = jax.jit(tiered3_queue_fill_rows, static_argnames=("kernels",))
_fill_tagged = jax.jit(tiered3_queue_fill_rows_tagged,
                       static_argnames=("kernels",))
_extract = jax.jit(tiered3_queue_extract, static_argnums=(1,),
                   static_argnames=("kernels",))


def _assert_queues_equal(qa, qb, msg=""):
    for f in qa._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(qa, f)), np.asarray(getattr(qb, f)),
            err_msg=f"{msg} field {f}",
        )


def _rand_rows(rng, n, num_types, arg_width, t_hi=10.0):
    t = rng.uniform(0, t_hi, n).astype(np.float32)
    ty = rng.integers(-1, num_types, n).astype(np.float32)
    a = rng.uniform(0, 1, (n, arg_width)).astype(np.float32)
    return jnp.asarray(np.concatenate([t[:, None], ty[:, None], a], axis=1))


def _run_differential(front_cap, stage_cap, capacity, *, steps, R, k,
                      seed, t_cap=8.0):
    """Drive identical random fill/extract streams through the XLA and
    Pallas paths and assert bit-equality after every operation."""
    rng = np.random.default_rng(seed)
    la = jnp.asarray([0.5, 1.0, 0.25], jnp.float32)
    W = 6
    qx = qp = tiered3_queue_init(
        capacity, front_cap=front_cap, stage_cap=stage_cap, arg_width=W
    )
    for step in range(steps):
        rows = _rand_rows(rng, R, la.shape[0], W)
        qx = _fill(qx, rows)
        qp = _fill(qp, rows, kernels="pallas")
        _assert_queues_equal(qx, qp, f"fill step {step}")
        if step % 3 == 2:
            cap = None if step % 2 else t_cap
            qx, ts1, ty1, a1, l1 = _extract(qx, k, la, cap)
            qp, ts2, ty2, a2, l2 = _extract(qp, k, la, cap, kernels="pallas")
            np.testing.assert_array_equal(np.asarray(ts1), np.asarray(ts2))
            np.testing.assert_array_equal(np.asarray(ty1), np.asarray(ty2))
            np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
            assert int(l1) == int(l2)
            _assert_queues_equal(qx, qp, f"extract step {step}")


def test_fill_extract_differential_small():
    _run_differential(16, 8, 64, steps=30, R=6, k=4, seed=0)


def test_fill_extract_differential_tiny_front():
    # front_cap == k: every extract drains the front, exercising the
    # refill + shift edge where length == front occupancy.
    _run_differential(4, 8, 64, steps=24, R=4, k=4, seed=1)


def test_tagged_fill_differential():
    """The sharded insert path (caller-supplied seqs + survive mask)."""
    rng = np.random.default_rng(2)
    la = jnp.asarray([0.5, 1.0], jnp.float32)
    W = 6
    qx = qp = tiered3_queue_init(64, front_cap=16, stage_cap=8, arg_width=W)
    next_seq = 0
    for step in range(20):
        rows = _rand_rows(rng, 5, la.shape[0], W)
        seqs = jnp.asarray(
            next_seq + np.arange(5, dtype=np.int32), jnp.int32
        )
        next_seq += 5
        insert = jnp.asarray(rng.random(5) < 0.8)
        qx = _fill_tagged(qx, rows, seqs, insert)
        qp = _fill_tagged(qp, rows, seqs, insert, kernels="pallas")
        _assert_queues_equal(qx, qp, f"tagged step {step}")


def test_window_extract_matches_reference_rule():
    """window_extract's take rule vs the shared window_prefix_mask spec
    applied to the same peeked front."""
    rng = np.random.default_rng(3)
    la = jnp.asarray([0.5, 1.0, 0.25], jnp.float32)
    W, k = 6, 4
    q = tiered3_queue_init(64, front_cap=16, stage_cap=8, arg_width=W)
    for _ in range(6):
        q = tiered3_queue_fill_rows(q, _rand_rows(rng, 6, 3, W))
    q, ts_c, tys_c, args_c, _ = tiered3_queue_peek_front(q, k)

    valid = tys_c >= 0
    lavec = la[jnp.clip(tys_c, 0, 2)]
    wins = jnp.where(valid, ts_c + lavec, jnp.inf)
    take = window_prefix_mask(ts_c, wins, valid, 5.0)

    ts, tys, args, length, *_ = window_extract(
        q.f_times, q.f_types, q.f_args, q.f_seqs, la, 5.0, k=k
    )
    np.testing.assert_array_equal(
        np.asarray(ts), np.asarray(jnp.where(take, ts_c, 0.0))
    )
    np.testing.assert_array_equal(
        np.asarray(tys), np.asarray(jnp.where(take, tys_c, 0))
    )
    np.testing.assert_array_equal(
        np.asarray(args), np.asarray(jnp.where(take[:, None], args_c, 0.0))
    )
    assert int(length) == int(jnp.sum(take))


def test_front_merge_empty_and_full_masks():
    """Degenerate masks: no row bound for the front, and all rows."""
    W, F, R = 6, 8, 4
    q = tiered3_queue_init(32, front_cap=F, stage_cap=8, arg_width=W)
    rng = np.random.default_rng(4)
    q = tiered3_queue_fill_rows(q, _rand_rows(rng, 4, 2, W, t_hi=4.0))

    t_r = jnp.asarray([1.0, 2.0, 3.0, 4.0], jnp.float32)
    ty_r = jnp.asarray([0, 1, 0, 1], jnp.int32)
    arg_r = jnp.zeros((R, W), jnp.float32)
    seq_r = jnp.asarray([100, 101, 102, 103], jnp.int32)

    for mask in (jnp.zeros((R,), bool), jnp.ones((R,), bool)):
        got = front_merge(
            q.f_times, q.f_types, q.f_args, q.f_seqs, q.front_n,
            t_r, ty_r, arg_r, seq_r, mask,
        )
        # XLA oracle: the _tiered_fill_finish front-merge block.
        from repro.core.queue import _I32_MAX, _small_lex_perm

        perm = _small_lex_perm(
            jnp.where(mask, t_r, jnp.inf),
            jnp.where(mask, seq_r, _I32_MAX),
        )
        rt = jnp.where(mask, t_r, jnp.inf)[perm]
        older = jnp.minimum(
            jnp.searchsorted(q.f_times, rt, side="right").astype(jnp.int32),
            q.front_n,
        )
        FE = F + R
        pos = jnp.where(
            mask[perm], older + jnp.arange(R, dtype=jnp.int32), FE + R
        )
        i_idx = jnp.arange(FE, dtype=jnp.int32)
        ins_before = jnp.searchsorted(pos, i_idx, side="left").astype(
            jnp.int32
        )
        is_ins = (
            jnp.searchsorted(pos, i_idx, side="right").astype(jnp.int32)
            > ins_before
        )
        src = jnp.where(
            is_ins, FE + jnp.clip(ins_before, 0, R - 1),
            jnp.clip(i_idx - ins_before, 0, FE - 1),
        )

        def fmerge(col, rcol, fill):
            ext = jnp.concatenate(
                [col, jnp.full((R,) + col.shape[1:], fill, col.dtype),
                 rcol]
            )
            return jnp.take(ext, src, axis=0)

        np.testing.assert_array_equal(
            np.asarray(got[0]), np.asarray(fmerge(q.f_times, rt, jnp.inf))
        )
        np.testing.assert_array_equal(
            np.asarray(got[1]),
            np.asarray(fmerge(q.f_types, ty_r[perm], -1)),
        )
        np.testing.assert_array_equal(
            np.asarray(got[2]),
            np.asarray(fmerge(q.f_args, arg_r[perm], 0.0)),
        )
        np.testing.assert_array_equal(
            np.asarray(got[3]),
            np.asarray(fmerge(q.f_seqs, seq_r[perm], 2**31 - 1)),
        )


def test_engine_pallas_parity_poc():
    """Whole-run parity: DeviceEngine(queue_kernels='pallas') vs XLA."""
    types = [0, 1, 0, 0, 1, 1, 0, 0, 1]

    def build():
        prog = poc.build_program(iters=64, config=Config(max_batch_len=3))
        for t, ty in enumerate(types):
            prog.schedule(float(t), ("Increment", "Set")[ty])
        return prog

    base = build().build(backend="device").run(poc.initial_state())
    pal = build().build(
        backend="device", queue_kernels="pallas"
    ).run(poc.initial_state())
    assert int(pal.state) == int(base.state)
    assert pal.batches == base.batches
    assert pal.events == base.events
    assert np.float32(pal.final_time) == np.float32(base.final_time)
    assert int(base.state) == poc.reference_final_sum(types, 64)


def test_pallas_requires_tiered3():
    prog = poc.build_program(iters=4)
    prog.schedule(0.0, "Increment")
    with pytest.raises(ValueError, match="pallas"):
        prog.build(backend="device", queue_mode="flat",
                   queue_kernels="pallas")


@pytest.mark.slow
@pytest.mark.parametrize("seed", [10, 11])
def test_fill_extract_differential_full_capacity(seed):
    """Full-size front/stage tiers under overflow pressure — the
    eviction, preflush, and refill paths all fire.

    Runs in a fresh interpreter: the interpret-mode sweep is sensitive
    to state a long pytest session accumulates (observed as a rare
    segfault only when run after the full suite; standalone it passes
    reliably), and isolation also keeps a crash from taking the whole
    session down with it.
    """
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(os.path.dirname(here), "src")
    script = (
        f"import sys; sys.path.insert(0, {here!r});"
        "from test_queue_kernels import _run_differential;"
        f"_run_differential(64, 32, 256, steps=60, R=24, k=8, "
        f"seed={seed}, t_cap=50.0)"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True)
    assert res.returncode == 0, \
        f"sweep subprocess exited {res.returncode}:\n{res.stderr[-3000:]}"


@pytest.mark.slow
def test_engine_pallas_parity_poc_long():
    rng = np.random.default_rng(12)
    types = list((rng.random(200) < 0.3).astype(int))

    def build(**kw):
        prog = poc.build_program(iters=16, config=Config(max_batch_len=4))
        for t, ty in enumerate(types):
            prog.schedule(float(t), ("Increment", "Set")[ty])
        return prog.build(backend="device", capacity=512, **kw)

    base = build().run(poc.initial_state())
    pal = build(queue_kernels="pallas").run(poc.initial_state())
    assert int(pal.state) == int(base.state)
    assert pal.batches == base.batches
    assert int(base.state) == poc.reference_final_sum(types, 16)


@pytest.mark.parametrize("backend,expected", [
    ("cpu", True), ("tpu", False), ("gpu", RuntimeError)])
def test_interpret_mode_by_backend(monkeypatch, backend, expected):
    """Interpret on CPU, Mosaic on TPU, and never a quiet interpret on
    any other backend."""
    from repro.kernels import interpret_mode

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if expected is RuntimeError:
        with pytest.raises(RuntimeError, match=backend):
            interpret_mode()
    else:
        assert interpret_mode() is expected
