"""Plain DEVStone HI: one sequential heap in Python, no JAX, no program code.

The reference for the DEVStone cells, written from the HI model's
definition (Wainer et al. 2011; Risco-Martín et al. 2024).  The coupled
model C_d, d = D .. 2, holds C_{d-1} and the atomics A_{d,1} .. A_{d,W-1};
its input goes to C_{d-1}'s input and to every A_{d,j}, and A_{d,j}'s
output to A_{d,j+1}'s input.  C_1 holds A_{1,1}, whose output goes up to
the top output, the sink.  An atomic counts each message it receives
(δext), holds it for ``prep_time``, then sends one message (λ) and counts
its internal transition (δint).  As a DES, three event types:

- ``IN`` (arg: level d): C_d's input; at d >= 2 it sends one message to
  each A_{d,j} and one to C_{d-1}, at d = 1 one to A_{1,1};
- ``EXT`` (arg: atomic): δext; schedules the atomic's ``INT`` after
  ``prep_time``;
- ``INT`` (arg: atomic): λ + δint; sends one ``EXT`` to the next atomic of
  the chain, or counts one message at the sink.

Atomic A_{d,j} (d >= 2) has id ``(d-2)(W-1) + j-1``; A_{1,1} is the last
id, ``(D-1)(W-1)``.  Every handler folds ``mix(time bits, 3*entity +
type)`` into an order-sensitive 32-bit checksum.  Emits draw global seqs
in commit order, an event's messages in the order listed above.

Events commit in ``(time, seq)`` order; a super-step takes, in that order,
up to ``max_batch_len`` events while each one's time is at most the least
``f32(t + lookahead)`` of those taken before it (the engine's §III-B
window rule), so the reference counts super-steps as the engine does and
can stop after the same number.

``control=True`` breaks the tie order: events at equal times commit last
in, first out.  At zero ``prep_time`` every event of an injection's
cascade is tied, so the cascade runs depth first instead of in seq order.
"""

from __future__ import annotations

import heapq
import struct

import numpy as np

IN, EXT, INT = 0, 1, 2
M32 = 0xFFFFFFFF
GRID = 256
_F32 = struct.Struct("<f")
_U32 = struct.Struct("<I")


def f32(x: float) -> float:
    """``x`` rounded to the nearest f32 (ties to even)."""
    return _F32.unpack(_F32.pack(x))[0]


def f32_bits(x: float) -> int:
    return _U32.unpack(_F32.pack(x))[0]


def num_atomics(width: int, depth: int) -> int:
    return (depth - 1) * (width - 1) + 1


def injection_times(cfg: dict, seed: int) -> list[float]:
    """Injection ``i`` at ``i * period + u_i``, ``u_i`` drawn from
    ``seed`` on a 1/256 grid in [0, 0.5)."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, GRID // 2, size=cfg["injections"])
    return [f32(i * cfg["period"] + k / GRID) for i, k in enumerate(u.tolist())]


def _mix(bits: int, key: int) -> int:
    h = (bits * 2654435761 + key * 40503 + 12345) & M32
    h ^= h >> 13
    h = (h * 0x5BD1E995) & M32
    return h ^ (h >> 15)


def simulate(cfg: dict, seed: int, batches: int, *, control: bool = False,
             track_peak: bool = False) -> dict:
    """Run ``batches`` super-steps from the seeded injections.
    ``track_peak`` adds ``peak_pending``, the most events pending at
    any super-step's end."""
    width, depth = cfg["width"], cfg["depth"]
    k, prep = cfg["max_batch_len"], f32(cfg["prep_time"])
    lookahead = {IN: 0.0, EXT: prep, INT: 0.0}
    n = num_atomics(width, depth)
    sink_atomic = n - 1
    ext_count = [0] * n
    int_count = [0] * n
    sink = 0
    checksum = 1
    order = -1 if control else 1
    heap = [(t, order * i, IN, depth)
            for i, t in enumerate(injection_times(cfg, seed))]
    heapq.heapify(heap)
    next_seq = len(heap)
    events = done = emitted = peak = 0
    last_t = 0.0
    pop, push = heapq.heappop, heapq.heappush
    while done < batches and heap:
        lim = float("inf")
        window = []
        while len(window) < k and heap and heap[0][0] <= lim:
            ev = pop(heap)
            window.append(ev)
            lim = min(lim, f32(ev[0] + lookahead[ev[2]]))
        for t, _, ty, x in window:
            checksum = (checksum * 31 + _mix(f32_bits(t), 3 * x + ty)) & M32
            if ty == IN:
                if x >= 2:
                    base = (x - 2) * (width - 1)
                    out = [(0.0, EXT, base + j) for j in range(width - 1)]
                    out.append((0.0, IN, x - 1))
                else:
                    out = [(0.0, EXT, sink_atomic)]
            elif ty == EXT:
                ext_count[x] += 1
                out = [(prep, INT, x)]
            else:
                int_count[x] += 1
                out = []
                if x == sink_atomic:
                    sink += 1
                elif x % (width - 1) < width - 2:
                    out = [(0.0, EXT, x + 1)]
            for delay, ety, ex in out:
                push(heap, (f32(t + delay), order * next_seq, ety, ex))
                next_seq += 1
            emitted += len(out)
        events += len(window)
        last_t = max(last_t, window[-1][0])
        done += 1
        peak = max(peak, len(heap))
    out = {
        "events": events, "batches": done, "final_time": last_t,
        "emitted": emitted, "pending": len(heap), "dropped": 0,
        "ext_count": np.asarray(ext_count, np.int64),
        "int_count": np.asarray(int_count, np.int64),
        "sink": sink, "checksum": checksum,
    }
    if track_peak:
        out["peak_pending"] = peak
    return out


def compare(dev: dict, ref: dict) -> list[tuple[str, float, float]]:
    """``(name, reading, limit)`` for every number compared; all exact."""
    seeded = dev["seeded"]

    def differs(key):
        return int(np.sum(np.asarray(dev[key], np.int64) != ref[key]))

    return [
        ("events_gap", abs(dev["events"] - ref["events"]), 0),
        ("batches_gap", abs(dev["batches"] - ref["batches"]), 0),
        ("final_time_gap", abs(dev["final_time"] - ref["final_time"]), 0),
        ("checksum_differs", int(dev["checksum"] != ref["checksum"]), 0),
        ("ext_count_differs", differs("ext_count"), 0),
        ("int_count_differs", differs("int_count"), 0),
        ("sink_gap", abs(dev["sink"] - ref["sink"]), 0),
        ("emitted_gap", abs(dev["emitted"] - ref["emitted"]), 0),
        ("pending_gap", abs(dev["pending"] - ref["pending"]), 0),
        ("dropped", dev["dropped"], 0),
        ("conservation_gap",
         abs(seeded + dev["emitted"] - dev["events"] - dev["pending"]
             - dev["dropped"]), 0),
    ]
