"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

A traced run wraps its sub-window in the host span ``bench.traced``;
that span's bounds are the window.  Device planes are ``/device:TPU:<n>``
and their ``XLA Ops`` line holds one event per executed operation.

- busy: the union of a device's operation intervals inside the window;
  idle is the window less busy.  Both are averaged over the devices.
- collective time: the union of the operations whose name is a
  collective (all-gather, all-reduce, ...), per device.
- device_ops: self time per operation (its time less that of the
  operations nested in it), averaged over devices.
- idle_gaps: device idle time by what the host was doing meanwhile: the
  innermost benchmark span or program frame that covers the middle of
  each gap (else the innermost host event there).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from pathlib import Path

import numpy as np

WINDOW_SPAN = "bench.traced"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|all-to-all|reduce-scatter|collective-permute"
    r"|allgather|allreduce|send|recv", re.IGNORECASE)


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge ``f64[n, 2]`` intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _total(iv: np.ndarray) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0


OP_NAME = re.compile(r"^(%?[\w.\-]+) = .*?\s([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``%fusion.12 fusion``."""
    m = OP_NAME.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def _self_times(ops: list, lo: float, hi: float):
    """Yield ``(short name, ns)``: each operation's time inside the
    window less the time of the operations nested in it (a while loop
    holds its body's operations on the same line)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    selfs, stack = [], []
    for i, (n, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        t = min(e, hi) - max(s, lo)
        selfs.append(t)
        if stack and ops[stack[-1]][2] >= e:
            selfs[stack[-1]] -= t
        stack.append(i)
    for (n, _, _), t in zip(ops, selfs):
        yield short_name(n), t


def _program_files() -> frozenset:
    """Basenames of the program's Python files, as the profiler's
    Python tracer names frames (``$engine.py:840 run``)."""
    src = Path(__file__).resolve().parents[1] / "src"
    return frozenset(p.name for p in src.rglob("*.py"))


def _label(name: str, files: frozenset) -> bool:
    """A host span worth naming a gap by: the benchmark's own spans and
    the program's Python frames."""
    return name.startswith("bench.") or (
        name.startswith("$") and name[1:].split(":")[0] in files)


def _label_gaps(gaps: np.ndarray, host: list, chunk: int = 2048):
    """Yield ``(label, ns)`` per gap: the innermost benchmark span or
    program frame covering the gap's middle, else the innermost host
    event there, else ``no host span``."""
    gaps = gaps[gaps[:, 1] > gaps[:, 0]]
    files = _program_files()
    names = [n for (n, _, _) in host] + ["no host span"]
    hs = np.asarray([s for (_, s, _) in host] + [-np.inf], np.float64)
    he = np.asarray([e for (_, _, e) in host] + [np.inf], np.float64)
    # Preferred spans sort before any other by a width offset.
    width = (he - hs) + np.asarray(
        [0.0 if _label(n, files) else 1e18 for n in names[:-1]] + [2e18])
    for i in range(0, len(gaps), chunk):
        g = gaps[i:i + chunk]
        mid = 0.5 * (g[:, 0] + g[:, 1])
        cover = (hs[None, :] <= mid[:, None]) & (he[None, :] >= mid[:, None])
        pick = np.argmin(np.where(cover, width[None, :], np.inf), axis=1)
        for j, t in zip(pick, g[:, 1] - g[:, 0]):
            yield names[j], float(t)


def read_events(xplane_path: str):
    """``(devices, host)``: per device id a list of ``(name, start_ns,
    end_ns)`` operations, and the host's spans as the same tuples."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.duration_ns > 0]
    return devices, host


def reduce_events(devices: dict, host: list, top: int = 10,
                  use: tuple | None = None) -> dict:
    """The numbers of one traced window, in seconds; ``use`` keeps only
    those device ids (the chips the cell runs on)."""
    if use is not None:
        devices = {d: ops for d, ops in devices.items() if d in use}
    spans = [(s, e) for (n, s, e) in host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no host span {WINDOW_SPAN!r} in the trace")
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    window_ns = hi - lo
    busy, coll, per_op = [], [], defaultdict(float)
    gaps_by_host = defaultdict(float)
    host_iv = [(n, s, e) for (n, s, e) in host if e > lo and s < hi]
    for dev in sorted(devices):
        ops = [o for o in devices[dev] if o[2] > lo and o[1] < hi]
        iv = np.asarray([(s, e) for (_, s, e) in ops], np.float64)
        u = _clip(_union(iv.reshape(-1, 2)), lo, hi)
        busy.append(_total(u))
        civ = np.asarray([(s, e) for (n, s, e) in ops if COLLECTIVE.search(n)],
                         np.float64)
        coll.append(_total(_clip(_union(civ.reshape(-1, 2)), lo, hi)))
        for n, t in _self_times(ops, lo, hi):
            per_op[n] += t / len(devices)
        edges = np.concatenate([[lo], u.reshape(-1), [hi]]).reshape(-1, 2)
        for label, t in _label_gaps(edges, host_iv):
            gaps_by_host[label] += t / len(devices)
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    n = len(devices)
    busy_ns = sum(busy) / n
    shares = [c / b for c, b in zip(coll, busy) if b > 0]
    ns = 1e-9
    return {
        "devices": n,
        "window_s": window_ns * ns,
        "busy_s": busy_ns * ns,
        "idle_s": (window_ns - busy_ns) * ns,
        "idle_share": 1.0 - busy_ns / window_ns,
        "collective_s": sum(coll) / n * ns,
        "collective_share": (sum(shares) / len(shares)) if shares else None,
        "has_collectives": any(c > 0 for c in coll),
        "device_ops": [[k, v * ns] for k, v in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * ns] for k, v in
                      sorted(gaps_by_host.items(), key=lambda kv: -kv[1])[:top]],
    }


def reduce_trace(xplane_path: str, use: tuple | None = None) -> dict:
    return reduce_events(*read_events(xplane_path), use=use)
