"""Plain PHOLD: one sequential heap in Python, no JAX, no program code.

The reference for the PHOLD cells, written from ROSS's PHOLD model: each
LP starts ``start_events`` events at ``lookahead + Exp(mean)``; an event
goes with probability ``remote`` to an LP drawn uniformly from all LPs,
else to its own, at ``now + lookahead + Exp(mean)``.  The draws are a
32-bit counter hash of ``(time bits, lp)``; the exponential is the f32
mid-point quantile table of ``2**16`` entries.  Every time is rounded to
f32 after each sum, as the device's f32 arithmetic rounds it.

Events commit in ``(time, seq)`` order; a super-step takes, in that
order, up to ``max_batch_len`` events with ``t <= f32(t_first +
lookahead)`` (the engine's window rule for one event type), so the
reference counts super-steps the way the engine does and can stop after
the same number.  Each event emits one, which draws the next global seq.

``control=True`` breaks the guarantee that a window's events commit one
after another: every event of a window reads the state as the window
found it, as a dispatch that ran the window's events side by side would
-- the per-LP counts still add up, the order-sensitive checksum keeps
the last event's write.  (Breaking the tie order instead does not show
at a window's horizon: the seed's seq order is its LP order.)
"""

from __future__ import annotations

import heapq
import struct

import numpy as np

M32 = 0xFFFFFFFF
TABLE_BITS = 16
_F32 = struct.Struct("<f")
_U32 = struct.Struct("<I")


def f32(x: float) -> float:
    """``x`` rounded to the nearest f32 (ties to even)."""
    return _F32.unpack(_F32.pack(x))[0]


def f32_bits(x: float) -> int:
    return _U32.unpack(_F32.pack(x))[0]


def exp_table(mean: float) -> list[float]:
    u = (np.arange(1 << TABLE_BITS, dtype=np.float64) + 0.5) / (1 << TABLE_BITS)
    return (-mean * np.log1p(-u)).astype(np.float32).astype(np.float64).tolist()


def initial_times(cfg: dict, seed: int, table: list[float]) -> list[float]:
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, 1 << TABLE_BITS,
                       size=cfg["num_lps"] * cfg["start_events"])
    la = float(cfg["lookahead"])
    return [f32(la + table[i]) for i in idx.tolist()]


def _mix(bits: int, lp: int) -> int:
    h = (bits * 2654435761 + lp * 40503 + 12345) & M32
    h ^= h >> 13
    h = (h * 0x5BD1E995) & M32
    return h ^ (h >> 15)


def simulate(cfg: dict, seed: int, batches: int, *, control: bool = False
             ) -> dict:
    """Run ``batches`` super-steps from the seeded start events."""
    num_lps, per_lp = cfg["num_lps"], cfg["start_events"]
    k, la = cfg["max_batch_len"], float(cfg["lookahead"])
    remote_below = int(round(cfg["remote"] * 65536))
    table = exp_table(cfg["mean"])
    times = initial_times(cfg, seed, table)
    heap = [(t, i, i // per_lp) for i, t in enumerate(times)]
    heapq.heapify(heap)
    next_seq = len(heap)
    counts = [0] * num_lps
    checksum = 1
    events = done = 0
    last_t = 0.0
    pop, push = heapq.heappop, heapq.heappush
    while done < batches and heap:
        lim = f32(heap[0][0] + la)
        window = []
        while len(window) < k and heap and heap[0][0] <= lim:
            window.append(pop(heap))
        start = checksum
        for t, _, lp in window:
            h = _mix(f32_bits(t), lp)
            delay = f32(la + table[h & 0xFFFF])
            if (h >> 16) < remote_below:
                g = ((h ^ (h >> 16)) * 0x45D9F3B) & M32
                dst = (g ^ (g >> 16)) % num_lps
            else:
                dst = lp
            counts[lp] += 1
            checksum = ((start if control else checksum) * 31 + h) & M32
            push(heap, (f32(t + delay), next_seq, dst))
            next_seq += 1
        events += len(window)
        last_t = max(last_t, window[-1][0])
        done += 1
    return {
        "events": events, "batches": done, "final_time": last_t,
        "emitted": events, "pending": len(heap), "dropped": 0,
        "counts": np.asarray(counts, np.int64), "checksum": checksum,
    }


def compare(dev: dict, ref: dict) -> list[tuple[str, float, float]]:
    """``(name, reading, limit)`` for every number compared; all exact."""
    seeded = dev["seeded"]
    return [
        ("events_gap", abs(dev["events"] - ref["events"]), 0),
        ("batches_gap", abs(dev["batches"] - ref["batches"]), 0),
        ("final_time_gap", abs(dev["final_time"] - ref["final_time"]), 0),
        ("checksum_differs", int(dev["checksum"] != ref["checksum"]), 0),
        ("lps_count_differs",
         int(np.sum(np.asarray(dev["counts"], np.int64) != ref["counts"])),
         0),
        ("emitted_gap", abs(dev["emitted"] - ref["emitted"]), 0),
        ("pending_gap", abs(dev["pending"] - ref["pending"]), 0),
        ("dropped", dev["dropped"], 0),
        ("conservation_gap",
         abs(seeded + dev["emitted"] - dev["events"] - dev["pending"]
             - dev["dropped"]), 0),
    ]
