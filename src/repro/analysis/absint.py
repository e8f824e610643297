"""Interval abstract interpretation over jaxprs.

The static analyzer (DESIGN.md §11) needs *bounds* on the values a
handler writes into its portable emit rows — the delay column decides
lookahead soundness, the type column decides the event-flow edges, and
``arg[0]`` is the sharded routing key.  Those cells are built from
constants, hashes folded through ``% k``, and ``jnp.where`` gates, so a
per-element interval domain recovers them exactly in the common case
while degrading soundly to *unknown* when a value is genuinely
data-dependent.

The domain: every jaxpr value is an :class:`Ival` — a pair of float64
numpy arrays ``(lo, hi)`` of the value's concrete shape, meaning "each
element lies in [lo, hi]".  ``(-inf, +inf)`` is unknown; ``lo == hi``
is a known constant.  Booleans are 0/1 intervals.  Interpretation
rules:

* If every input of an equation is known, the primitive is *executed*
  (``prim.bind``) — exact constant folding, including whole ``jit``
  sub-jaxprs, ``iota``, scatters of constants, even ``while`` loops
  over constants.
* Otherwise a per-primitive transfer function propagates intervals.
  Structural primitives (slice/concat/broadcast/scatter-with-known-
  indices) are emulated positionally on the lo and hi arrays, which is
  what preserves per-CELL precision for emit rows built with
  ``emits.at[r, c].set(...)``.
* Any primitive without a rule falls back to unknown — the analysis
  never *invents* a bound.

Integer soundness: after every equation the result is checked against
the output dtype's range; a bound that escapes the representable range
(possible wraparound) widens to the full dtype range instead of being
clipped.  This is what makes ``unknown_u32 * 2654435761`` come out as
``[0, 2^32)`` (true under wraparound) and then ``% 8`` as ``[0, 7]``.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np

from jax.extend.core import Literal

_NEG_INF = float("-inf")
_POS_INF = float("inf")

# Exact-integer ceiling for the float64 carrier: int64 constants above
# this are not exactly representable, so they degrade to unknown rather
# than silently rounding.
_EXACT_INT_MAX = float(2**53)


class Ival(NamedTuple):
    """Per-element interval: two float64 arrays of the value's shape."""

    lo: np.ndarray
    hi: np.ndarray

    @property
    def known(self) -> bool:
        """True when every element is pinned to a single value."""
        return bool(np.all(self.lo == self.hi))


def const_ival(x) -> Ival:
    a = np.asarray(x)
    if np.issubdtype(a.dtype, np.bool_):
        a = a.astype(np.float64)
    a = np.asarray(a, np.float64)
    if a.size and np.issubdtype(np.asarray(x).dtype, np.integer):
        if float(np.max(np.abs(a), initial=0.0)) > _EXACT_INT_MAX:
            return Ival(np.full(a.shape, _NEG_INF),
                        np.full(a.shape, _POS_INF))
    return Ival(a, a.copy())


def _dtype_range(dtype) -> tuple[float, float]:
    dtype = np.dtype(dtype)
    if dtype == np.bool_:
        return 0.0, 1.0
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        return float(info.min), float(info.max)
    return _NEG_INF, _POS_INF


def unknown_ival(aval) -> Ival:
    lo, hi = _dtype_range(aval.dtype)
    shape = tuple(aval.shape)
    return Ival(np.full(shape, lo), np.full(shape, hi))


def _guard(iv: Ival, aval) -> Ival:
    """Sound dtype post-condition: any element whose bound escapes the
    output dtype's range may have wrapped — widen IT (not clip it) to
    the full range.  NaN bounds also widen."""
    lo_d, hi_d = _dtype_range(aval.dtype)
    lo = np.broadcast_to(np.asarray(iv.lo, np.float64),
                         tuple(aval.shape)).copy()
    hi = np.broadcast_to(np.asarray(iv.hi, np.float64),
                         tuple(aval.shape)).copy()
    bad = (np.isnan(lo) | np.isnan(hi) | (lo > hi))
    if math.isfinite(lo_d):  # integer / bool dtype
        bad |= (lo < lo_d) | (hi > hi_d)
    lo[bad] = lo_d
    hi[bad] = hi_d
    return Ival(lo, hi)


def _hull(*ivs: Ival) -> Ival:
    lo = ivs[0].lo
    hi = ivs[0].hi
    for iv in ivs[1:]:
        lo = np.minimum(lo, iv.lo)
        hi = np.maximum(hi, iv.hi)
    return Ival(lo, hi)


def hull_scalar(iv: Ival, shape) -> Ival:
    """Collapse to the global [min, max] of the array, broadcast to
    ``shape`` — the sound fallback for data-dependent indexing."""
    lo = float(np.min(iv.lo)) if iv.lo.size else _NEG_INF
    hi = float(np.max(iv.hi)) if iv.hi.size else _POS_INF
    return Ival(np.full(tuple(shape), lo), np.full(tuple(shape), hi))


# ---------------------------------------------------------------------------
# transfer functions
# ---------------------------------------------------------------------------

def _mul_iv(a: Ival, b: Ival) -> Ival:
    with np.errstate(all="ignore"):
        prods = np.stack(np.broadcast_arrays(
            a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi))
    bad = np.isnan(prods).any(axis=0)
    lo = np.where(bad, _NEG_INF,
                  np.min(np.where(np.isnan(prods), _POS_INF, prods), axis=0))
    hi = np.where(bad, _POS_INF,
                  np.max(np.where(np.isnan(prods), _NEG_INF, prods), axis=0))
    return Ival(lo, hi)


def _div_iv(a: Ival, b: Ival, *, integer: bool) -> Ival:
    # Divisor interval touching 0 -> unknown.
    crosses = (b.lo <= 0) & (b.hi >= 0)
    with np.errstate(all="ignore"):
        qs = np.stack(np.broadcast_arrays(
            a.lo / b.lo, a.lo / b.hi, a.hi / b.lo, a.hi / b.hi))
    bad = np.isnan(qs).any(axis=0) | np.broadcast_to(
        crosses, qs.shape[1:])
    lo = np.where(bad, _NEG_INF,
                  np.min(np.where(np.isnan(qs), _POS_INF, qs), axis=0))
    hi = np.where(bad, _POS_INF,
                  np.max(np.where(np.isnan(qs), _NEG_INF, qs), axis=0))
    if integer:  # lax int div truncates toward zero: within [floor, ceil]
        with np.errstate(invalid="ignore"):
            lo = np.floor(lo)
            hi = np.ceil(hi)
    return Ival(lo, hi)


def _rem_iv(a: Ival, b: Ival, *, integer: bool, pymod: bool) -> Ival:
    """C-style ``lax.rem`` (sign of dividend) or Python-style mod.

    Sound whenever the divisor is bounded; exactness needs a positive
    divisor bound.  The dividend may be completely unknown — that is
    the whole point (hash % k)."""
    d_hi = np.maximum(np.abs(b.lo), np.abs(b.hi))
    pos = b.lo > 0
    bounded = np.isfinite(d_hi)
    slack = 0.0 if integer else np.where(bounded, 0.0, 0.0)
    del slack
    mag = np.where(bounded, d_hi - (1.0 if integer else 0.0), _POS_INF)
    mag = np.maximum(mag, 0.0)
    if pymod:
        # result sign follows the divisor; positive divisor -> [0, d).
        lo = np.where(pos & bounded, 0.0, -np.where(bounded, mag, _POS_INF))
        hi = np.where(bounded, mag, _POS_INF)
    else:
        nonneg_dividend = a.lo >= 0
        lo = np.where(nonneg_dividend, 0.0,
                      -np.where(bounded, mag, _POS_INF))
        hi = np.where(bounded, mag, _POS_INF)
    lo, hi = np.broadcast_arrays(
        *np.broadcast_arrays(lo, hi, a.lo)[:2])
    return Ival(np.asarray(lo, np.float64).copy(),
                np.asarray(hi, np.float64).copy())


def _cmp(a: Ival, b: Ival, op: str) -> Ival:
    one = np.float64(1.0)
    zero = np.float64(0.0)
    if op == "lt":
        t, f = a.hi < b.lo, a.lo >= b.hi
    elif op == "le":
        t, f = a.hi <= b.lo, a.lo > b.hi
    elif op == "gt":
        t, f = a.lo > b.hi, a.hi <= b.lo
    elif op == "ge":
        t, f = a.lo >= b.hi, a.hi < b.lo
    elif op == "eq":
        t = (a.lo == a.hi) & (b.lo == b.hi) & (a.lo == b.lo)
        f = (a.hi < b.lo) | (a.lo > b.hi)
    else:  # ne
        f = (a.lo == a.hi) & (b.lo == b.hi) & (a.lo == b.lo)
        t = (a.hi < b.lo) | (a.lo > b.hi)
    t, f = np.broadcast_arrays(t, f)
    lo = np.where(t, one, zero)
    hi = np.where(f, zero, one)
    return Ival(lo, hi)


def _select_n(pred: Ival, cases: list[Ival], out_shape) -> Ival:
    if pred.known:
        idx = pred.lo.astype(np.int64)
        lo = np.zeros(tuple(out_shape))
        hi = np.zeros(tuple(out_shape))
        idx_b = np.broadcast_to(idx, tuple(out_shape))
        for i, c in enumerate(cases):
            sel = idx_b == i
            lo = np.where(sel, np.broadcast_to(c.lo, tuple(out_shape)), lo)
            hi = np.where(sel, np.broadcast_to(c.hi, tuple(out_shape)), hi)
        return Ival(lo, hi)
    lo = np.broadcast_to(cases[0].lo, tuple(out_shape)).astype(np.float64)
    hi = np.broadcast_to(cases[0].hi, tuple(out_shape)).astype(np.float64)
    for c in cases[1:]:
        lo = np.minimum(lo, np.broadcast_to(c.lo, tuple(out_shape)))
        hi = np.maximum(hi, np.broadcast_to(c.hi, tuple(out_shape)))
    return Ival(lo, hi)


def _broadcast_in_dim(x: Ival, shape, broadcast_dimensions) -> Ival:
    def expand(a):
        newshape = [1] * len(shape)
        for src, dst in enumerate(broadcast_dimensions):
            newshape[dst] = a.shape[src]
        return np.broadcast_to(np.reshape(a, newshape), tuple(shape))
    return Ival(expand(x.lo), expand(x.hi))


def _scatter_points(eqn, op: Ival, idx: Ival, upd: Ival, *, add: bool):
    """Emulate the common ``arr.at[i, j].set/add(scalar)`` scatter with
    KNOWN indices: scalar updates addressed by full-rank points.
    Returns None when the form is more general (caller falls back)."""
    dn = eqn.params["dimension_numbers"]
    if (tuple(dn.update_window_dims) != ()
            or tuple(getattr(dn, "operand_batching_dims", ())) != ()
            or tuple(getattr(dn, "scatter_indices_batching_dims", ())) != ()):
        return None
    if not idx.known:
        return None
    if not add and not eqn.params.get("unique_indices", False):
        return None  # duplicate set scatters are order-dependent
    operand_rank = op.lo.ndim
    if tuple(sorted(dn.inserted_window_dims)) != tuple(range(operand_rank)):
        return None
    s2o = tuple(dn.scatter_dims_to_operand_dims)
    indices = idx.lo.astype(np.int64)
    if indices.ndim == 0:
        indices = indices.reshape(1)
    # last axis of the indices array is the index vector
    vec = indices.shape[-1]
    if vec != len(s2o):
        return None
    pts = indices.reshape(-1, vec)
    upd_lo = np.broadcast_to(upd.lo, pts.shape[:1]
                             if upd.lo.ndim else ()).reshape(-1)
    upd_hi = np.broadcast_to(upd.hi, pts.shape[:1]
                             if upd.hi.ndim else ()).reshape(-1)
    if upd_lo.size != pts.shape[0]:
        upd_lo = np.asarray(upd.lo, np.float64).reshape(-1)
        upd_hi = np.asarray(upd.hi, np.float64).reshape(-1)
        if upd_lo.size != pts.shape[0]:
            return None
    lo = op.lo.copy()
    hi = op.hi.copy()
    shape = op.lo.shape
    for p in range(pts.shape[0]):
        coord = [0] * operand_rank
        ok = True
        for j, d in enumerate(s2o):
            c = int(pts[p, j])
            if not 0 <= c < shape[d]:
                ok = False  # FILL_OR_DROP: out-of-range updates drop
                break
            coord[d] = c
        if not ok:
            continue
        coord = tuple(coord)
        if add:
            lo[coord] += upd_lo[p]
            hi[coord] += upd_hi[p]
        else:
            lo[coord] = upd_lo[p]
            hi[coord] = upd_hi[p]
    return Ival(lo, hi)


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------

_SUBJAXPR_PRIMS = {
    "jit", "closed_call", "core_call", "remat", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "custom_jvp_call_jaxpr",
}


def _sub_jaxpr(eqn):
    for key in ("jaxpr", "call_jaxpr"):
        if key in eqn.params:
            return eqn.params[key]
    return None


def _try_exact(eqn, ins: list[Ival]):
    """All inputs known -> run the primitive for an exact result."""
    vals = []
    for v, iv in zip(eqn.invars, ins):
        dtype = v.aval.dtype
        a = iv.lo
        if np.issubdtype(dtype, np.integer) and a.size:
            if float(np.max(np.abs(a), initial=0.0)) > _EXACT_INT_MAX:
                return None
        vals.append(np.asarray(a).astype(dtype).reshape(
            tuple(v.aval.shape)))
    try:
        out = eqn.primitive.bind(*vals, **eqn.params)
    except Exception:
        return None
    outs = out if eqn.primitive.multiple_results else [out]
    return [const_ival(np.asarray(o)) for o in outs]


def eval_jaxpr_ivals(jaxpr, consts, in_ivals: list[Ival]) -> list[Ival]:
    """Interpret an (open) jaxpr over the interval domain."""
    env: dict = {}

    def read(atom) -> Ival:
        if isinstance(atom, Literal):
            return const_ival(np.asarray(atom.val))
        return env[atom]

    def write(var, iv: Ival):
        env[var] = _guard(iv, var.aval)

    for v, c in zip(jaxpr.constvars, consts):
        write(v, const_ival(np.asarray(c)))
    for v, iv in zip(jaxpr.invars, in_ivals):
        write(v, iv)

    for eqn in jaxpr.eqns:
        ins = [read(x) for x in eqn.invars]
        outs = None
        if all(iv.known for iv in ins):
            outs = _try_exact(eqn, ins)
        if outs is None:
            outs = _rule(eqn, ins)
        if outs is None:
            outs = [unknown_ival(v.aval) for v in eqn.outvars]
        for v, iv in zip(eqn.outvars, outs):
            write(v, iv)
    return [read(v) for v in jaxpr.outvars]


def _rule(eqn, ins: list[Ival]):  # noqa: C901 - one big transfer table
    name = eqn.primitive.name
    out_avals = [v.aval for v in eqn.outvars]
    aval = out_avals[0]

    if name in _SUBJAXPR_PRIMS:
        sub = _sub_jaxpr(eqn)
        if sub is None:
            return None
        # jnp.remainder lowers to jit[name=remainder]; the generic
        # recursion loses the sign correction (select over an unknown
        # predicate), so apply the Python-mod rule directly — it is
        # exact for positive divisors regardless of the dividend.
        if eqn.params.get("name") == "remainder" and len(ins) == 2:
            integer = np.issubdtype(aval.dtype, np.integer)
            out = _rem_iv(ins[0], ins[1], integer=integer, pymod=True)
            return [Ival(np.broadcast_to(out.lo, tuple(aval.shape)).copy(),
                         np.broadcast_to(out.hi, tuple(aval.shape)).copy())]
        inner = getattr(sub, "jaxpr", sub)
        consts = getattr(sub, "consts", ())
        try:
            return eval_jaxpr_ivals(inner, consts, ins)
        except Exception:
            return None

    if name == "add":
        return [Ival(ins[0].lo + ins[1].lo, ins[0].hi + ins[1].hi)]
    if name == "sub":
        return [Ival(ins[0].lo - ins[1].hi, ins[0].hi - ins[1].lo)]
    if name == "mul":
        return [_mul_iv(ins[0], ins[1])]
    if name == "div":
        return [_div_iv(ins[0], ins[1],
                        integer=np.issubdtype(aval.dtype, np.integer))]
    if name == "rem":
        return [_rem_iv(ins[0], ins[1],
                        integer=np.issubdtype(aval.dtype, np.integer),
                        pymod=False)]
    if name == "neg":
        return [Ival(-ins[0].hi, -ins[0].lo)]
    if name == "abs":
        spans = (ins[0].lo <= 0) & (ins[0].hi >= 0)
        lo = np.where(spans, 0.0,
                      np.minimum(np.abs(ins[0].lo), np.abs(ins[0].hi)))
        hi = np.maximum(np.abs(ins[0].lo), np.abs(ins[0].hi))
        return [Ival(lo, hi)]
    if name == "sign":
        return [Ival(np.sign(ins[0].lo), np.sign(ins[0].hi))]
    if name in ("max", "min"):
        f = np.maximum if name == "max" else np.minimum
        return [Ival(f(ins[0].lo, ins[1].lo), f(ins[0].hi, ins[1].hi))]
    if name in ("floor", "ceil", "round", "round_nearest_even"):
        f = {"floor": np.floor, "ceil": np.ceil}.get(name, np.round)
        return [Ival(f(ins[0].lo), f(ins[0].hi))]
    if name in ("sin", "cos"):
        # Not monotone; the exact path handles known inputs, so the
        # range bound is all that is needed here.
        return [Ival(np.full(tuple(aval.shape), -1.0),
                     np.full(tuple(aval.shape), 1.0))]
    if name in ("exp", "tanh", "logistic", "sqrt", "log", "log1p",
                "expm1"):
        f = {"exp": np.exp, "tanh": np.tanh,
             "logistic": lambda x: 1.0 / (1.0 + np.exp(-x)),
             "sqrt": lambda x: np.sqrt(np.maximum(x, 0.0)),
             "log": lambda x: np.log(np.maximum(x, 0.0)),
             "log1p": lambda x: np.log1p(np.maximum(x, -1.0)),
             "expm1": np.expm1}[name]
        with np.errstate(all="ignore"):
            return [Ival(f(ins[0].lo), f(ins[0].hi))]
    if name == "integer_pow":
        y = eqn.params["y"]
        if y < 0:
            return None
        lo_p, hi_p = ins[0].lo ** y, ins[0].hi ** y
        if y % 2 == 0:
            spans = (ins[0].lo <= 0) & (ins[0].hi >= 0)
            lo = np.where(spans, 0.0, np.minimum(lo_p, hi_p))
            hi = np.maximum(lo_p, hi_p)
        else:
            lo, hi = lo_p, hi_p
        return [Ival(lo, hi)]
    if name == "convert_element_type":
        src = eqn.invars[0].aval.dtype
        lo, hi = ins[0].lo, ins[0].hi
        if (np.issubdtype(np.dtype(aval.dtype), np.integer)
                and np.issubdtype(np.dtype(src), np.floating)):
            with np.errstate(invalid="ignore"):
                lo, hi = np.trunc(lo), np.trunc(hi)  # C-style truncation
        return [Ival(lo, hi)]  # _guard widens on wraparound
    if name == "stop_gradient" or name == "copy":
        return [ins[0]]
    if name in ("lt", "le", "gt", "ge", "eq", "ne"):
        return [_cmp(ins[0], ins[1], name)]
    if name == "not":
        if np.dtype(aval.dtype) == np.bool_:
            return [Ival(1.0 - ins[0].hi, 1.0 - ins[0].lo)]
        return None
    if name in ("and", "or", "xor"):
        a, b = ins
        if np.dtype(aval.dtype) == np.bool_:
            if name == "and":
                return [Ival(np.minimum(a.lo, b.lo) * 0
                             + a.lo * b.lo, np.minimum(a.hi, b.hi))]
            if name == "or":
                return [Ival(np.maximum(a.lo, b.lo),
                             np.minimum(1.0, a.hi + b.hi))]
            return [Ival(np.zeros(np.broadcast_shapes(a.lo.shape,
                                                      b.lo.shape)),
                         np.ones(np.broadcast_shapes(a.hi.shape,
                                                     b.hi.shape)))]
        if np.all(a.lo >= 0) and np.all(b.lo >= 0):
            if name == "and":
                return [Ival(np.zeros_like(a.lo + b.lo),
                             np.minimum(a.hi, b.hi))]
            return [Ival(np.zeros_like(a.lo + b.lo), a.hi + b.hi)]
        return None
    if name in ("shift_right_logical", "shift_right_arithmetic"):
        a, k = ins
        if not k.known:
            return None
        scale = 2.0 ** k.lo
        if name == "shift_right_logical" and np.any(a.lo < 0):
            return None
        with np.errstate(all="ignore"):
            return [Ival(np.floor(a.lo / scale), np.floor(a.hi / scale))]
    if name == "shift_left":
        a, k = ins
        if not k.known:
            return None
        return [_mul_iv(a, const_ival(2.0 ** k.lo))]
    if name == "select_n":
        return [_select_n(ins[0], ins[1:], aval.shape)]
    if name == "clamp":
        lo_b, x, hi_b = ins
        return [Ival(np.clip(x.lo, lo_b.lo, hi_b.hi),
                     np.clip(x.hi, lo_b.lo, hi_b.hi))]
    if name == "broadcast_in_dim":
        return [_broadcast_in_dim(ins[0], aval.shape,
                                  eqn.params["broadcast_dimensions"])]
    if name == "reshape":
        x = ins[0]
        dims = eqn.params.get("dimensions")
        lo, hi = x.lo, x.hi
        if dims is not None:
            lo, hi = np.transpose(lo, dims), np.transpose(hi, dims)
        return [Ival(lo.reshape(tuple(aval.shape)),
                     hi.reshape(tuple(aval.shape)))]
    if name == "squeeze":
        dims = tuple(eqn.params["dimensions"])
        return [Ival(np.squeeze(ins[0].lo, axis=dims),
                     np.squeeze(ins[0].hi, axis=dims))]
    if name == "expand_dims":
        dims = tuple(eqn.params["dimensions"])
        return [Ival(np.expand_dims(ins[0].lo, dims),
                     np.expand_dims(ins[0].hi, dims))]
    if name == "transpose":
        perm = eqn.params["permutation"]
        return [Ival(np.transpose(ins[0].lo, perm),
                     np.transpose(ins[0].hi, perm))]
    if name == "rev":
        dims = tuple(eqn.params["dimensions"])
        return [Ival(np.flip(ins[0].lo, dims), np.flip(ins[0].hi, dims))]
    if name == "slice":
        sl = tuple(
            slice(s, l, None if st is None else st)
            for s, l, st in zip(
                eqn.params["start_indices"], eqn.params["limit_indices"],
                eqn.params["strides"] or [None] * len(
                    eqn.params["start_indices"]))
        )
        return [Ival(ins[0].lo[sl], ins[0].hi[sl])]
    if name == "concatenate":
        dim = eqn.params["dimension"]
        return [Ival(np.concatenate([iv.lo for iv in ins], axis=dim),
                     np.concatenate([iv.hi for iv in ins], axis=dim))]
    if name == "pad":
        x, pv = ins
        cfg = eqn.params["padding_config"]
        if any(i != 0 for (_, _, i) in cfg) or any(
                l < 0 or h < 0 for (l, h, _) in cfg):
            return None
        pads = tuple((l, h) for (l, h, _) in cfg)
        return [Ival(
            np.pad(x.lo, pads, constant_values=float(np.min(pv.lo))),
            np.pad(x.hi, pads, constant_values=float(np.max(pv.hi))))]
    if name == "dynamic_slice":
        x, *starts = ins
        if all(s.known for s in starts):
            sizes = eqn.params["slice_sizes"]
            idx = []
            for d, (s, size) in enumerate(zip(starts, sizes)):
                start = int(np.clip(float(s.lo), 0,
                                    x.lo.shape[d] - size))
                idx.append(slice(start, start + size))
            idx = tuple(idx)
            return [Ival(x.lo[idx], x.hi[idx])]
        return [hull_scalar(x, aval.shape)]
    if name == "dynamic_update_slice":
        x, upd, *starts = ins
        if all(s.known for s in starts):
            lo, hi = x.lo.copy(), x.hi.copy()
            idx = []
            for d, s in enumerate(starts):
                start = int(np.clip(float(s.lo), 0,
                                    x.lo.shape[d] - upd.lo.shape[d]))
                idx.append(slice(start, start + upd.lo.shape[d]))
            idx = tuple(idx)
            lo[idx], hi[idx] = upd.lo, upd.hi
            return [Ival(lo, hi)]
        u_lo = float(np.min(upd.lo)) if upd.lo.size else 0.0
        u_hi = float(np.max(upd.hi)) if upd.hi.size else 0.0
        return [Ival(np.minimum(x.lo, u_lo), np.maximum(x.hi, u_hi))]
    if name == "gather":
        return [hull_scalar(ins[0], aval.shape)]
    if name in ("scatter", "scatter-add"):
        op, idx, upd = ins
        add = name == "scatter-add"
        out = _scatter_points(eqn, op, idx, upd, add=add)
        if out is not None:
            return [out]
        if add:
            u_lo = float(np.sum(np.minimum(upd.lo, 0.0)))
            u_hi = float(np.sum(np.maximum(upd.hi, 0.0)))
            return [Ival(op.lo + u_lo, op.hi + u_hi)]
        u_lo = float(np.min(upd.lo)) if upd.lo.size else 0.0
        u_hi = float(np.max(upd.hi)) if upd.hi.size else 0.0
        return [Ival(np.minimum(op.lo, u_lo), np.maximum(op.hi, u_hi))]
    if name == "reduce_sum":
        axes = tuple(eqn.params["axes"])
        return [Ival(np.sum(ins[0].lo, axis=axes),
                     np.sum(ins[0].hi, axis=axes))]
    if name in ("reduce_max", "reduce_min"):
        axes = tuple(eqn.params["axes"])
        f = np.max if name == "reduce_max" else np.min
        return [Ival(f(ins[0].lo, axis=axes), f(ins[0].hi, axis=axes))]
    if name in ("reduce_or", "reduce_and"):
        axes = tuple(eqn.params["axes"])
        f = np.max if name == "reduce_or" else np.min
        return [Ival(f(ins[0].lo, axis=axes), f(ins[0].hi, axis=axes))]
    if name in ("argmax", "argmin"):
        n = 1
        for d in tuple(eqn.params["axes"]):
            n *= eqn.invars[0].aval.shape[d]
        return [Ival(np.full(tuple(aval.shape), 0.0),
                     np.full(tuple(aval.shape), float(n - 1)))]
    if name == "iota":
        # no inputs, so the exact path normally handles it; keep a rule
        # for completeness.
        dim = eqn.params["dimension"]
        shape = tuple(eqn.params["shape"])
        vals = np.reshape(
            np.arange(shape[dim], dtype=np.float64),
            tuple(shape[dim] if i == dim else 1
                  for i in range(len(shape))))
        vals = np.broadcast_to(vals, shape).copy()
        return [Ival(vals, vals.copy())]
    return None
