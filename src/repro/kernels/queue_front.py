"""Pallas kernels for the tiered3 queue's front-tier hot loops.

The XLA shapes of these two loops (all-pairs rank + gather +
``dynamic_slice``) were tuned for XLA:CPU, where sort custom calls and
scatters carry large fixed overhead (DESIGN.md §4.4).  These kernels
run the same math as ONE Pallas program per call with every operand
resident in VMEM:

* :func:`window_extract` — the §III-B dynamic-lookahead take rule over
  the (already refilled) sorted front plus the prefix pop.
* :func:`front_merge` — the front counting-merge of the per-batch emit
  insert (:func:`repro.core.queue._tiered_fill_finish`): lex-rank the
  emit rows, locate each insertion point against the sorted front, and
  rebuild the merged ``front_cap + R`` columns — no sorts, no gathers.

Both are BIT-IDENTICAL to the XLA paths (the differential suites in
``tests/test_queue_kernels.py`` pin this).  Selected via
``DeviceEngine(queue_kernels="pallas")`` /
``tiered3_queue_extract(..., kernels="pallas")``.

Layout.  The wrappers pack the queue's columns into ONE int32 tile
array, one column per sublane row (time and args as their f32 bit
patterns), with the slot index on lanes padded to a multiple of 128 by
free-slot sentinels.  Every selection inside a kernel is then a masked
select or a lane roll of whole rows, which keeps the bits exact
(including ``-0.0`` and ``inf``) and keeps Mosaic on aligned 2-D
vectors.  Scalars (lookaheads, the horizon cap, ``front_n``) travel in
SMEM.  Off-TPU the kernels run in interpret mode
(:func:`repro.kernels.interpret_mode`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

_I32_MAX = 2**31 - 1
_INF_BITS = 0x7F800000          # f32 +inf as int32 bits
_FAR = 2**30                    # insertion position of rows not merged
_LANES = 128
_SUBLANES = 8
_TIME, _TYPE, _SEQ, _ARG0 = 0, 1, 2, 3   # packed row of each column


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _bits(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _f32(x):
    return jax.lax.bitcast_convert_type(x, jnp.float32)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _fill_rows(shape):
    """Free-slot sentinel per packed row: time inf, type -1, seq max,
    args 0 — broadcast over ``shape`` (rows on axis 0)."""
    r = _iota(shape, 0)
    return jnp.where(r == _TIME, _INF_BITS,
                     jnp.where(r == _TYPE, -1,
                               jnp.where(r == _SEQ, _I32_MAX, 0)))


def _pack(times, types, seqs, args, width: int):
    """Columns -> ``i32[round_up(3 + W, 8), width]``, slot on lanes,
    lanes past the column length holding the free-slot sentinel."""
    n, W = args.shape
    rows = [_bits(jnp.asarray(times, jnp.float32)),
            jnp.asarray(types, jnp.int32),
            jnp.asarray(seqs, jnp.int32)]
    rows += [_bits(args[:, w].astype(jnp.float32)) for w in range(W)]
    C = _round_up(len(rows), _SUBLANES)
    packed = jnp.stack(rows)
    if C > len(rows):
        packed = jnp.concatenate(
            [packed, jnp.zeros((C - len(rows), n), jnp.int32)])
    if width > n:
        packed = jnp.concatenate([packed, _fill_rows((C, width - n))],
                                 axis=1)
    return packed


def _unpack(packed, n: int, W: int):
    """Inverse of :func:`_pack` over the first ``n`` lanes."""
    p = packed[:, :n]
    args = _f32(p[_ARG0:_ARG0 + W]).T
    return _f32(p[_TIME]), p[_TYPE], args, p[_SEQ]


# ---------------------------------------------------------------------------
# Window extract (take rule + prefix pop)
# ---------------------------------------------------------------------------

def _window_extract_kernel(la_ref, cap_ref, p_ref, win_ref, len_ref, out_ref,
                           *, k: int, F: int):
    C, FP = p_ref.shape
    KL = win_ref.shape[1]
    KP = _round_up(k, _SUBLANES)
    T = la_ref.shape[0]

    head = p_ref[:, :KL]
    t = _f32(p_ref[_TIME:_TIME + 1, :KL])                  # [1, KL]
    y = p_ref[_TYPE:_TYPE + 1, :KL]
    valid = y >= 0
    yc = jnp.clip(y, 0, T - 1)
    la = jnp.zeros(t.shape, jnp.float32)
    for ty in range(T):                                    # SMEM lookup
        la = jnp.where(yc == ty, la_ref[ty], la)
    wins = jnp.where(valid, t + la, jnp.inf)

    # Candidate i on sublanes, slot j on lanes.  Exclusive cummin of
    # the window bounds; the candidate's own time/type by diagonal
    # select; the prefix-AND stop rule is "length = first rejection".
    i2 = _iota((KP, KL), 0)
    j2 = _iota((KP, KL), 1)
    t_max = jnp.min(jnp.where(j2 < i2, wins, jnp.inf), axis=1, keepdims=True)
    t_i = jnp.min(jnp.where(j2 == i2, t, jnp.inf), axis=1, keepdims=True)
    v_i = jnp.max(jnp.where(j2 == i2, y, -1), axis=1, keepdims=True) >= 0
    i_col = _iota((KP, 1), 0)
    ok = v_i & (t_i <= jnp.minimum(t_max, cap_ref[0])) & (i_col < k)
    length = jnp.min(jnp.where(ok, k, i_col), axis=0, keepdims=True)

    win_ref[...] = jnp.where(_iota((C, KL), 1) < length, head, 0)
    len_ref[...] = jnp.broadcast_to(length, len_ref.shape)

    # Prefix pop: shift every row left by `length`, one static lane
    # roll per bit of it, then sentinel-fill the vacated tail.
    x = p_ref[...]
    for b in range(k.bit_length()):
        bit = ((length >> b) & 1) == 1
        x = jnp.where(bit, pltpu.roll(x, FP - (1 << b), 1), x)
    lane = _iota((C, FP), 1)
    out_ref[...] = jnp.where(lane + length < F, x, _fill_rows((C, FP)))


@partial(jax.jit, static_argnames=("k", "interpret"))
def window_extract(f_times, f_types, f_args, f_seqs, lookaheads,
                   t_cap=None, *, k: int, interpret: bool | None = None):
    """Fused take-rule + pop over a refilled sorted front tier.

    Bit-identical to ``window_prefix_mask`` + ``tiered3_queue_pop_prefix``
    applied to the same front columns.  Returns
    ``(ts[k], tys[k], args[k, W], length, f_times', f_types', f_args',
    f_seqs')`` with the primed columns shifted left by ``length``.
    """
    F = f_times.shape[0]
    W = f_args.shape[1]
    if k > F:
        raise ValueError(f"window width {k} exceeds front capacity {F}")
    interpret = interpret_mode() if interpret is None else interpret
    FP = _round_up(F, _LANES)
    KL = _round_up(k, _LANES)
    packed = _pack(f_times, f_types, f_seqs, f_args, FP)
    C = packed.shape[0]
    cap = (jnp.full((1,), jnp.inf, jnp.float32) if t_cap is None
           else jnp.asarray(t_cap, jnp.float32).reshape(1))
    la = jnp.asarray(lookaheads, jnp.float32)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    win, length, out = pl.pallas_call(
        partial(_window_extract_kernel, k=k, F=F),
        out_shape=[
            jax.ShapeDtypeStruct((C, KL), jnp.int32),
            jax.ShapeDtypeStruct((_SUBLANES, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((C, FP), jnp.int32),
        ],
        in_specs=[smem, smem, vmem],
        out_specs=[vmem, vmem, vmem],
        interpret=interpret,
    )(la, cap, packed)
    ts, tys, args, _ = _unpack(win, k, W)
    nt, ny, na, ns = _unpack(out, F, W)
    return ts, tys, args, length[0, 0], nt, ny, na, ns


# ---------------------------------------------------------------------------
# Front counting-merge (the per-batch emit insert hot loop)
# ---------------------------------------------------------------------------

def _front_merge_kernel(fn_ref, p_ref, e_ref, m_ref, out_ref,
                        *, F: int, R: int):
    C, FEP = p_ref.shape
    RL = e_ref.shape[1]
    RP = _round_up(R, _SUBLANES)

    # Emit rows: row e on lanes (row form) and, by diagonal select, on
    # sublanes (column form) — exact for int32 bit patterns.
    ie = _iota((RP, RL), 0)
    je = _iota((RP, RL), 1)
    diag = ie == je

    def col(row):
        return jnp.max(jnp.where(diag, row, jnp.iinfo(jnp.int32).min),
                       axis=1, keepdims=True)

    mask = (m_ref[...] != 0) & (_iota((1, RL), 1) < R)
    # Rows not bound for the front sort last: (inf, I32_MAX) keys.
    tt = jnp.where(mask, e_ref[_TIME:_TIME + 1, :], _INF_BITS)
    ss = jnp.where(mask, e_ref[_SEQ:_SEQ + 1, :], _I32_MAX)
    tt_row = _f32(tt)
    tt_col = _f32(col(tt))
    ss_col = col(ss)
    ins_col = col(mask.astype(jnp.int32)) != 0

    # Lex rank of each row by (time, seq, index) — unique in [0, R).
    before = (tt_row < tt_col) | (
        (tt_row == tt_col)
        & ((ss < ss_col) | ((ss == ss_col) & (je < ie)))
    )
    rank = jnp.sum(jnp.where(before & (je < R), 1, 0), axis=1,
                   keepdims=True)

    # searchsorted(f_times, t, 'right') as an all-pairs count, capped at
    # the live occupancy (emit seqs exceed queued seqs, so rows land
    # after every equal-time slot).
    ft = _f32(p_ref[_TIME:_TIME + 1, :])
    lf = _iota((RP, FEP), 1)
    older = jnp.minimum(
        jnp.sum(jnp.where((ft <= tt_col) & (lf < F), 1, 0), axis=1,
                keepdims=True),
        fn_ref[0],
    )
    pos = jnp.where(ins_col, older + rank, _FAR)            # [RP, 1]

    # Output slot i on lanes: inserted rows before it, and whether a row
    # lands exactly there.
    ins_before = jnp.sum(jnp.where(pos < lf, 1, 0), axis=0, keepdims=True)
    hit = pos == lf                                         # [RP, FEP]
    is_ins = jnp.max(jnp.where(hit, 1, 0), axis=0, keepdims=True) > 0

    # Front slots move right by the inserted-row count before them: one
    # static lane roll per possible count (the padded tail is sentinel,
    # which is what the evicted overflow region reads).
    p = p_ref[...]
    front = p
    for s in range(1, R + 1):
        front = jnp.where(ins_before == s, pltpu.roll(p, s, 1), front)

    # Inserted slots take their row's packed column values.
    r_idx = _iota((C, FEP), 0)
    ins = jnp.zeros((C, FEP), jnp.int32)
    for c in range(C):
        val = jnp.sum(jnp.where(hit, col(e_ref[c:c + 1, :]), 0), axis=0,
                      keepdims=True)
        ins = jnp.where(r_idx == c, val, ins)
    out_ref[...] = jnp.where(is_ins, ins, front)


@partial(jax.jit, static_argnames=("interpret",))
def front_merge(f_times, f_types, f_args, f_seqs, front_n,
                t_r, ty_r, arg_r, seq_r, to_front, *,
                interpret: bool | None = None):
    """Counting-merge ``R`` emit rows into the sorted front tier.

    Bit-identical to the XLA front-merge block of
    :func:`repro.core.queue._tiered_fill_finish`: returns the merged
    ``(times, types, args, seqs)`` columns, ``front_cap + R`` wide —
    slots ``[front_cap:]`` are the evicted tail the caller stages.
    ``to_front`` is the rows-bound-for-the-front mask (insert-surviving
    AND earlier than the tier boundary).
    """
    F = f_times.shape[0]
    R = t_r.shape[0]
    W = f_args.shape[1]
    interpret = interpret_mode() if interpret is None else interpret
    FE = F + R
    FEP = _round_up(FE, _LANES)
    RL = _round_up(R, _LANES)
    front = _pack(f_times, f_types, f_seqs, f_args, FEP)
    emits = _pack(t_r, ty_r, seq_r, jnp.asarray(arg_r, jnp.float32), RL)
    mask = jnp.zeros((1, RL), jnp.int32).at[0, :R].set(
        jnp.asarray(to_front, jnp.int32))
    fn = jnp.asarray(front_n, jnp.int32).reshape(1)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        partial(_front_merge_kernel, F=F, R=R),
        out_shape=jax.ShapeDtypeStruct(front.shape, jnp.int32),
        in_specs=[smem, vmem, vmem, vmem],
        out_specs=vmem,
        interpret=interpret,
    )(fn, front, emits, mask)
    mt, my, ma, ms = _unpack(out, FE, W)
    return mt, my, ma, ms
