"""Per-leg device time and device idle from a profiler trace, by the
program's ``des.*`` names (``src/repro/core/spans.py``).

The device side: each TPU op's event metadata carries a ``tf_op`` stat,
the JAX name stack of the op (``jit(_run)/while/body/des.extract/...``).
``jax.profiler.ProfileData`` does not expose event-metadata stats, so
the ``.xplane.pb`` is read here by a small protobuf wire reader, with no
dependency beyond the standard library.  An op's self time (its time
less that of the ops nested in it on the ``XLA Ops`` line) goes to the
innermost ``des.*`` component of its ``tf_op``.  An op with no ``tf_op``
(a ``while``, a ``conditional``, a layout ``copy``) takes the scope that
holds most of the time of the ops nested in it, unless one of them is
outside every scope; failing that, the scope of the op that encloses
it; failing that, ``unscoped``.  An op whose ``tf_op`` names no
``des.*`` scope (the loop guard, the carry) is ``unscoped``, except one
named after a loop or branch construct itself (``jit(_run)/while``),
which the compiler made for that construct: it is treated as having no
``tf_op``.  Self times
are cut from the union of the ops' intervals, so the scopes sum to the
device's busy time exactly.

The host side: the loop thread is the host line that holds the
``des.segment`` spans.  Each stretch of device idle time goes to the
innermost ``des.*`` span on that thread over it, else to
``unspanned``; other threads' spans (the stream feeder's) never label
it.

The window is the host span ``bench.traced`` when the trace has one,
else the extent of the device ops.  Reduce one's own trace with::

    python3 bench/scopes.py <profiler output dir or .xplane.pb> [--steps N]
"""

from __future__ import annotations

import json
import re
import struct
import sys
from collections import defaultdict
from dataclasses import dataclass, field

from xplane import DEVICE_PLANE, OPS_LINE, WINDOW_SPAN, find_xplane, short_name

SCOPE = re.compile(r"(?:^|/)(des\.[A-Za-z0-9_]+(?:\.[A-Za-z0-9_]+)*)")
# The engine loop's guard: its ops run once per super-step (and once
# more to exit), so their count checks that the trace kept every step.
GUARD = re.compile(r"^jit\([^)]*\)/while/cond/")
# A name stack that ends at a loop or branch construct: the compiler made
# the op for the construct itself (a carry copy), not for any leg in it.
CONTROL = re.compile(r"(?:^|/)(?:while|cond|switch)(?::[^/]*)?$")
UNSCOPED = "unscoped"
UNSPANNED = "unspanned"
SEGMENT = "des.segment"
BOUNDARY = "des.boundary"
ABSORB = "des.absorb"
LEGS = {"extract_us": "des.extract", "dispatch_us": "des.dispatch",
        "insert_us": "des.insert", "merge_us": "des.merge"}


# ---------------------------------------------------------------------------
# protobuf wire reader for XSpace (tsl/profiler/protobuf/xplane.proto)
# ---------------------------------------------------------------------------

@dataclass
class Line:
    name: str
    # (metadata id, start ns, end ns, raw stats bytes or None)
    events: list = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: list = field(default_factory=list)
    names: dict = field(default_factory=dict)   # metadata id -> name
    stats: dict = field(default_factory=dict)   # metadata id -> {stat: value}
    stat_names: dict = field(default_factory=dict)
    buf: memoryview | None = None                # host planes: the file


def _varint(b, i):
    x = b[i]
    i += 1
    if x < 0x80:
        return x, i
    x &= 0x7F
    shift = 7
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _int64(x):
    return x - (1 << 64) if x >= 1 << 63 else x


def _fields(b, i, end):
    """Yield ``(field, wire type, value)`` of the message ``b[i:end]``;
    a length-delimited value is its ``(start, end)`` range."""
    while i < end:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            n, i = _varint(b, i)
            v = (i, i + n)
            i += n
        elif wt == 1:
            v = b[i:i + 8]
            i += 8
        elif wt == 5:
            v = b[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt} at byte {i}")
        yield f, wt, v


def _str(b, r):
    return bytes(b[r[0]:r[1]]).decode("utf-8", "replace")


def _stats(b, ranges, stat_names):
    """Decode ``XStat`` messages into ``{stat name: value}``."""
    out = {}
    for r in ranges:
        mid, val = None, None
        for f, wt, v in _fields(b, *r):
            if f == 1:
                mid = _int64(v)
            elif f == 2:
                val = struct.unpack("<d", v)[0]
            elif f == 3:
                val = v
            elif f == 4:
                val = _int64(v)
            elif f in (5, 6):
                val = _str(b, v)
            elif f == 7:
                val = stat_names.get(_int64(v), v)
        out[stat_names.get(mid, mid)] = val
    return out


def _event_metadata(b, r, plane, pending):
    mid, name, stats = 0, "", []
    for f, wt, v in _fields(b, *r):
        if f == 1:
            mid = _int64(v)
        elif f == 2:
            name = _str(b, v)
        elif f == 5:
            stats.append(v)
    plane.names[mid] = name
    if stats:
        pending[mid] = stats


def _line(b, r, keep_stats):
    name, ts, events = "", 0, []
    for f, wt, v in _fields(b, *r):
        if f == 2:
            name = _str(b, v)
        elif f == 3:
            ts = _int64(v)
        elif f == 4:
            events.append(v)
    line = Line(name)
    base = ts * 1000
    for lo, hi in events:
        mid = off = dur = 0
        stats = None
        i = lo
        while i < hi:
            key, i = _varint(b, i)
            if key == 8:          # metadata_id
                mid, i = _varint(b, i)
                mid = _int64(mid)
            elif key == 16:       # offset_ps
                off, i = _varint(b, i)
            elif key == 24:       # duration_ps
                dur, i = _varint(b, i)
            elif key & 7 == 2:    # stats (field 4), skipped unless kept
                n, i = _varint(b, i)
                if keep_stats and key >> 3 == 4:
                    stats = stats or []
                    stats.append((i, i + n))
                i += n
            elif key & 7 == 0:    # num_occurrences
                _, i = _varint(b, i)
            else:
                raise ValueError(f"unexpected XEvent field key {key}")
        # The profiler's own rounding: whole nanoseconds, truncated.
        start = (base + _int64(off)) // 1000
        line.events.append((mid, start, start + _int64(dur) // 1000, stats))
    return line


def read_xspace(path: str) -> list:
    """The planes of an ``.xplane.pb`` as :class:`Plane` objects.

    Device planes keep each op metadata's stats (``tf_op``); host
    planes keep each event's stats as raw byte ranges, decoded on
    demand by :func:`event_stats`."""
    with open(path, "rb") as fh:
        b = memoryview(fh.read())
    planes = []
    for f, wt, r in _fields(b, 0, len(b)):
        if f != 1:
            continue
        plane, lines, meta, pending = Plane(""), [], [], {}
        for pf, pwt, pv in _fields(b, *r):
            if pf == 2:
                plane.name = _str(b, pv)
            elif pf == 3:
                lines.append(pv)
            elif pf == 4:
                meta.append(pv)
            elif pf == 5:   # map<int64, XStatMetadata>
                for ef, ewt, ev in _fields(b, *pv):
                    if ef == 2:
                        sid, sname = 0, ""
                        for sf, swt, sv in _fields(b, *ev):
                            if sf == 1:
                                sid = _int64(sv)
                            elif sf == 2:
                                sname = _str(b, sv)
                        plane.stat_names[sid] = sname
        for r_entry in meta:   # map<int64, XEventMetadata>
            for ef, ewt, ev in _fields(b, *r_entry):
                if ef == 2:
                    _event_metadata(b, ev, plane, pending)
        for mid, ranges in pending.items():
            plane.stats[mid] = _stats(b, ranges, plane.stat_names)
        host = plane.name.startswith("/host:")
        plane.lines = [_line(b, lr, keep_stats=host) for lr in lines]
        plane.buf = b if host else None
        planes.append(plane)
    return planes


def event_stats(plane: Plane, event) -> dict:
    """``{stat name: value}`` of one host event (its span arguments)."""
    stats = event[3]
    return {} if not stats else _stats(plane.buf, stats, plane.stat_names)


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def scope_of(tf_op: str | None) -> str | None:
    """The innermost ``des.*`` component of a name stack; ``unscoped``
    for a stack with none; ``None`` (no scope of its own) for no stack
    or one that names only a loop or branch construct."""
    if tf_op is None:
        return None
    found = SCOPE.findall(tf_op)
    if found:
        return found[-1]
    return None if CONTROL.search(tf_op) else UNSCOPED


def _sweep(iv: list, lo: int, hi: int, pieces: list | None = None):
    """Cut ``[(start, end), ...]`` clipped to ``[lo, hi)`` into its
    union, each instant owned by the innermost interval over it (the
    latest started of those still open).  Returns ``(own, parent,
    order)``: per interval the time it owns and the index of the
    interval open around its start (``-1`` for none), and the indices
    by start.  ``pieces`` collects ``(start, end, index)`` in time
    order when given."""
    n = len(iv)
    order = sorted(range(n), key=lambda k: (iv[k][0], -iv[k][1]))
    own, parent = [0] * n, [-1] * n
    stack, t = [], lo

    def run_to(x):
        nonlocal t
        while stack and t < x:
            top = stack[-1]
            e = min(iv[top][1], hi)
            if e <= t:
                stack.pop()
                continue
            nt = min(e, x)
            own[top] += nt - t
            if pieces is not None:
                pieces.append((t, nt, top))
            t = nt

    for k in order:
        s, e = max(iv[k][0], lo), min(iv[k][1], hi)
        if e <= s:
            continue
        run_to(s)
        while stack and min(iv[stack[-1]][1], hi) <= s:
            stack.pop()
        t = max(t, s)
        parent[k] = stack[-1] if stack else -1
        stack.append(k)
    run_to(hi)
    return own, parent, order


def _resolve(own_scope: list, own: list, parent: list, order: list) -> list:
    """Scopes of the ops with no ``tf_op`` (``None`` in ``own_scope``).

    Such an op takes the scope that holds most of the self time of the
    ops nested in it, unless one of those is outside every scope (the
    loop guard: the op then spans more than one leg).  The compiler
    moves a few small ops between neighbouring computations, so a
    switch's branches can hold a stray op of the insert: a majority,
    not unanimity.  Failing that, the op takes its encloser's scope,
    else ``unscoped``."""
    n = len(own_scope)
    res = list(own_scope)
    inner = [None] * n      # scope -> nested self time; False: mixed in
    for k in reversed(order):          # children before their parents
        if res[k] is None and inner[k]:
            res[k] = max(inner[k], key=lambda sc: (inner[k][sc], sc))
        p = parent[k]
        if p < 0 or inner[p] is False:
            continue
        if res[k] == UNSCOPED or inner[k] is False:
            inner[p] = False
            continue
        acc = inner[p] if inner[p] is not None else {}
        for sc, t in (inner[k] or {}).items():
            acc[sc] = acc.get(sc, 0) + t
        if res[k] is not None:
            acc[res[k]] = acc.get(res[k], 0) + own[k]
        inner[p] = acc
    for k in order:                    # parents before their children
        if res[k] is None:
            p = parent[k]
            res[k] = res[p] if p >= 0 and res[p] is not None else UNSCOPED
    return res


def _overlaps(a: list, b: list) -> int:
    """Total overlap of two sorted lists of disjoint intervals."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _label_overlaps(gaps: list, pieces: list, names: list, out: dict,
                    weight: float):
    """Add each gap's time to the labels of the pieces over it, the
    rest to ``unspanned``; both lists sorted and disjoint."""
    j = 0
    for gs, ge in gaps:
        covered = 0
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            s, e = max(gs, pieces[k][0]), min(ge, pieces[k][1])
            if e > s:
                out[names[pieces[k][2]]] += (e - s) * weight
                covered += e - s
            k += 1
        out[UNSPANNED] += (ge - gs - covered) * weight


def device_ops(planes: list, use: tuple | None = None) -> dict:
    """Per device id: ``[(start ns, end ns, name, tf_op or None)]`` of
    the ``XLA Ops`` line."""
    out = {}
    for p in planes:
        m = DEVICE_PLANE.match(p.name)
        if not m or (use is not None and int(m.group(1)) not in use):
            continue
        ops = []
        for line in p.lines:
            if line.name == OPS_LINE:
                ops += [(s, e, p.names.get(mid, ""),
                         p.stats.get(mid, {}).get("tf_op"))
                        for (mid, s, e, _) in line.events]
        out[int(m.group(1))] = ops
    return out


def host_lines(planes: list) -> list:
    """``[[(name, start ns, end ns, args), ...], ...]``: one list per
    host thread, spans of nonzero length; ``args`` decoded for the
    ``des.*`` and ``bench.*`` spans only."""
    out = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            evs = []
            for ev in line.events:
                if ev[2] <= ev[1]:
                    continue
                name = p.names.get(ev[0], "")
                args = (event_stats(p, ev)
                        if name.startswith(("des.", "bench.")) else {})
                evs.append((name, ev[1], ev[2], args))
            out.append(evs)
    return out


def reduce_scopes(devices: dict, hosts: list, steps: int | None = None,
                  top: int = 10) -> dict:
    """Per-scope device time and idle by loop-thread span, in seconds, over
    the window.

    ``coverage`` says whether the trace kept every device event:
    ``events`` counts op events starting in the first and in the last
    tenth of the window; given ``steps`` (super-steps in the window),
    ``per_step`` divides them by a tenth of the steps, and ``guard``
    gives the runs of the loop guard's most frequent op per super-step
    in the first tenth, the last tenth and the whole window: 1.0 where
    no event was lost, less where the profiler dropped some."""
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    spans = [(s, e) for line in hosts for (n, s, e, _) in line
             if n == WINDOW_SPAN]
    if spans:
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    else:
        lo = min(s for ops in devices.values() for (s, _, _, _) in ops)
        hi = max(e for ops in devices.values() for (_, e, _, _) in ops)
    # The loop thread: the host line holding the segment spans.
    loop = max(hosts, key=lambda ln: sum(n == SEGMENT for (n, *_) in ln),
                 default=[])
    if not any(n == SEGMENT for (n, *_) in loop):
        loop = []
    dspans = [ev for ev in loop if ev[0].startswith("des.")]
    pieces = []
    _sweep([(s, e) for (_, s, e, _) in dspans], lo, hi, pieces)
    span_names = [n for (n, *_) in dspans]

    def mid_in(s, e):
        return lo <= (s + e) / 2 <= hi

    # A span belongs to the window when its middle lies in it.
    bounds = sorted((max(s, lo), min(e, hi)) for (n, s, e, _) in dspans
                    if n == BOUNDARY and mid_in(s, e))

    n_dev = len(devices)
    scopes, unscoped = defaultdict(float), defaultdict(float)
    idle_by = defaultdict(float)
    busy = idle_boundary = 0.0
    cov_first = cov_last = 0.0
    guard = [0.0, 0.0, 0.0]
    tenth = (hi - lo) / 10
    for dev in sorted(devices):
        ops = [o for o in devices[dev] if o[1] > lo and o[0] < hi]
        own, parent, order = _sweep([(s, e) for (s, e, _, _) in ops],
                                    lo, hi)
        res = _resolve([scope_of(o[3]) for o in ops], own, parent, order)
        for k, t in enumerate(own):
            if t:
                scopes[res[k]] += t / n_dev
                if res[k] == UNSCOPED:
                    unscoped[short_name(ops[k][2])] += t / n_dev
        busy_ns = sum(own)
        busy += busy_ns / n_dev
        # idle gaps: the window less the union of the ops
        union = sorted((max(s, lo), min(e, hi)) for (s, e, _, _) in ops)
        gaps, t = [], lo
        for s, e in union:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        _label_overlaps(gaps, pieces, span_names, idle_by, 1.0 / n_dev)
        idle_boundary += _overlaps(gaps, bounds) / n_dev
        cov_first += sum(lo <= s < lo + tenth for (s, *_) in ops) / n_dev
        cov_last += sum(hi - tenth <= s < hi for (s, *_) in ops) / n_dev
        runs = defaultdict(list)
        for (s, _, n, tf) in ops:
            if tf and GUARD.match(tf):
                runs[n].append(s)
        if runs:
            starts = max(runs.values(), key=len)
            guard[0] += sum(lo <= s < lo + tenth for s in starts) / n_dev
            guard[1] += sum(hi - tenth <= s < hi for s in starts) / n_dev
            guard[2] += len(starts) / n_dev

    per_step = None if not steps else steps / 10
    ns = 1e-9
    window = hi - lo
    scopes.setdefault(UNSCOPED, 0.0)
    return {
        "window_s": window * ns,
        "busy_s": busy * ns,
        "idle_s": (window - busy) * ns,
        "scopes": {k: v * ns for k, v in sorted(scopes.items())},
        "unscoped_ops": [[k, v * ns] for k, v in sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:top]],
        "idle_by_span": {k: v * ns for k, v in sorted(
            idle_by.items(), key=lambda kv: -kv[1]) if v > 0},
        "boundaries": len(bounds),
        "boundary_idle_s": idle_boundary * ns,
        "segments": sum(1 for (n, s, e, _) in loop
                        if n == SEGMENT and mid_in(s, e)),
        "absorbed_rows": sum(int(a.get("rows", 0)) for (n, s, e, a) in loop
                             if n == ABSORB and mid_in(s, e)),
        "coverage": {
            "events": [cov_first, cov_last],
            "per_step": (None if per_step is None else
                         [cov_first / per_step, cov_last / per_step]),
            "guard": (None if per_step is None else
                      [guard[0] / per_step, guard[1] / per_step,
                       guard[2] / steps]),
        },
    }


def reduce_file(path: str, steps: int | None = None,
                use: tuple | None = None) -> dict:
    planes = read_xspace(path)
    return reduce_scopes(device_ops(planes, use), host_lines(planes), steps)


def legs(red: dict, steps: int | None) -> dict:
    """The per-leg numbers of one reduced window: device µs per
    super-step by scope, and device idle ms per segment boundary."""
    out = {k: (red["scopes"].get(v, 0.0) / steps * 1e6 if steps else None)
           for k, v in LEGS.items()}
    out["boundary_idle_ms"] = (red["boundary_idle_s"] / red["boundaries"]
                               * 1e3 if red["boundaries"] else None)
    return out


def main(argv=None) -> int:
    import argparse
    import os

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="profiler output dir or .xplane.pb file")
    ap.add_argument("--steps", type=int, default=None,
                    help="super-steps in the window, for per-step numbers")
    args = ap.parse_args(argv)
    path = (find_xplane(args.trace) if os.path.isdir(args.trace)
            else args.trace)
    red = reduce_file(path, args.steps)
    red["legs"] = legs(red, args.steps)
    print(json.dumps(red, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
