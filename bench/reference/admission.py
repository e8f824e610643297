"""Plain open admission: one sequential heap in Python, no JAX, no
program code.

The reference for the streamed admission cell.  The initial ``TICK``
holds seq 0 and arrival ``j`` seq ``1 + j`` (the arrivals' reserved
range); emitted events draw seqs from ``1 + n`` on, in commit order.
Everything commits in ``(time, seq)`` order, so after the same number of
events the reference holds the state the engine must hold.

The feeder takes one block per segment boundary, and block ``j >= 1`` is
taken only when the engine has reached its first arrival, which it then
commits next; so after ``E`` events the rows taken are the blocks whose
first arrival is among them, plus block 0.

``control=True`` breaks the ordering guarantee: events at equal times
commit by type (ARRIVE, ADMIT, TICK) before seq -- the order a window
grouped by type would give.
"""

from __future__ import annotations

import heapq

import numpy as np

ARRIVE, ADMIT, TICK = 0, 1, 2
M32 = 0xFFFFFFFF


def _i32(x: int) -> int:
    x &= M32
    return x - (1 << 32) if x >= (1 << 31) else x


def _hash_mod(k: int, salt: int, mod: int) -> int:
    h = _i32(_i32(k + salt) * 1103515245)
    if h != -(1 << 31):
        h = abs(h)
    return h % mod


def simulate(cfg: dict, arrivals: np.ndarray, n_requests: int,
             block_size: int, events: int, *, control: bool = False
             ) -> dict:
    """Commit ``events`` events; ``arrivals`` are the source's rows."""
    max_decode = cfg["max_decode"]
    slots = np.zeros(cfg["num_slots"], np.int64)
    st = dict(waiting=0, arrivals=0, admitted=0, served=0, decoded=0,
              retries=0)
    arr_t = arrivals[:, 0].astype(np.float64).tolist()
    n_arr = len(arr_t)

    def key(t, ty, seq):
        return (t, ty, seq) if control else (t, seq, ty)

    heap = [key(1.0, TICK, 0)]
    next_seq = 1 + n_requests
    nxt = 0                      # next arrival not yet committed
    done = emitted = 0
    last_t = 0.0
    first_of_block = set(range(block_size, n_arr, block_size))
    blocks_taken = 1 if n_arr else 0
    while done < events:
        if nxt < n_arr:
            a = key(arr_t[nxt], ARRIVE, 1 + nxt)
            if not heap or a < heap[0]:
                heapq.heappush(heap, a)
                if nxt in first_of_block:
                    blocks_taken += 1
                nxt += 1
        if not heap:
            break
        ev = heapq.heappop(heap)
        t = ev[0]
        ty = ev[1] if control else ev[2]
        emit = None
        if ty == ARRIVE:
            k = st["arrivals"]
            st["arrivals"] = k + 1
            st["waiting"] += 1
            emit = (t + 0.25, ADMIT)
        elif ty == ADMIT:
            free = slots <= 0
            any_free = bool(free.any())
            have_wait = st["waiting"] > 0
            if have_wait and any_free:
                slots[int(np.argmax(free))] = 1 + _hash_mod(
                    st["admitted"], 977, max_decode)
                st["waiting"] -= 1
                st["admitted"] += 1
            if have_wait and not any_free:
                st["retries"] += 1
                emit = (t + 1.0, ADMIT)
        else:
            active = slots > 0
            slots[active] -= 1
            st["served"] += int(np.sum(active & (slots == 0)))
            st["decoded"] += int(np.sum(active))
            if (st["arrivals"] < n_requests or st["waiting"] > 0
                    or bool((slots > 0).any())):
                emit = (t + 1.0, TICK)
        if emit is not None:
            heapq.heappush(heap, key(emit[0], emit[1], next_seq))
            next_seq += 1
            emitted += 1
        done += 1
        last_t = max(last_t, t)
    ingested = min(blocks_taken * block_size, n_arr)
    return dict(
        st, slots=slots, events=done, final_time=last_t, emitted=emitted,
        ingested=ingested, pending=len(heap) + (ingested - nxt),
        dropped=0,
    )


def compare(dev: dict, ref: dict) -> list[tuple[str, float, float]]:
    """``(name, reading, limit)`` for every number compared; all exact."""
    out = [
        ("events_gap", abs(dev["events"] - ref["events"]), 0),
        ("final_time_gap", abs(dev["final_time"] - ref["final_time"]), 0),
        ("slots_differ",
         int(np.sum(np.asarray(dev["slots"], np.int64) != ref["slots"])), 0),
    ]
    for name in ("waiting", "arrivals", "admitted", "served", "decoded",
                 "retries", "emitted", "ingested"):
        out.append((f"{name}_gap", abs(int(dev[name]) - int(ref[name])), 0))
    out += [
        ("pending_gap",
         abs(dev["pending"] + dev["spilled"] - ref["pending"]), 0),
        ("dropped", dev["dropped"] + dev["shed"], 0),
        ("conservation_gap",
         abs(dev["seeded"] + dev["ingested"] + dev["emitted"] - dev["events"]
             - dev["pending"] - dev["dropped"] - dev["spilled"]
             - dev["shed"]), 0),
    ]
    return out
