"""Host→device arrival feeding: the double-buffered :class:`StreamFeeder`.

The feeder sits between an :class:`~repro.stream.source.ArrivalSource`
and the segment loop in :meth:`repro.core.program.CompiledSim.run`.  A
daemon thread pulls blocks from the source, validates them, assigns
seqs from the run's reserved range, and stages both the host copy and a
``jax.device_put`` device copy into a depth-2 queue — so while the
engine executes the active segment (releasing the GIL inside XLA), the
NEXT arrival block's generation and host→device transfer overlap with
device compute.  ``prefetch=False`` degrades to synchronous in-line
feeding (the bench baseline for measuring that overlap).

Determinism: the feeder never *decides* anything — which rows are
admitted, shed, or spilled is chosen by the segment loop from the
cursor, the horizon, and queue occupancy, all of which are independent
of thread timing.  Prefetching only changes WHEN a block's bytes reach
the device, never what they contain.

Seq discipline (the equivalence keystone): the run reserves seqs
``seq0 .. seq0+len(source)`` upfront by advancing the queue's global
``next_seq`` before the first batch, and the feeder labels global row
``j`` with seq ``seq0 + j``.  An arrival therefore occupies exactly the
(time, seq) rank it would have had as the ``j``-th pre-seeded event,
even under timestamp ties with events emitted mid-run (which draw seqs
past the reserved range).  Shed rows leave harmless seq gaps.
"""

from __future__ import annotations

import queue as _queue
import threading
import time
import warnings

import numpy as np

import jax

from repro.core.spans import FEEDER_STAGE
from repro.stream.source import EMIT_WIDTH, ArrivalSource

_I32_MAX = 2**31 - 1

#: blocks staged ahead of the consumer: the active block + one standby
_DEPTH = 2


class StreamFeeder:
    """Cursor-tracking, optionally prefetching view over an arrival source.

    The consumer (the segment loop) sees a flat row stream addressed by
    a global ``cursor`` (row index into the source) and interacts at
    block granularity:

    - :meth:`next_key` — the (time, seq) lex key of the next unconsumed
      arrival, or ``(inf, 2**31-1)`` when exhausted.  This is the
      admission fence fed to the engine: no event at/after this key may
      execute before the arrival is absorbed.
    - :meth:`admissible` — how many rows of the *current block* have
      ``time <= t_end`` (arrivals past the horizon are never consumed).
    - :meth:`device_block` / :meth:`host_slice` — the staged device
      arrays (for the jitted masked absorb) or a host copy of the next
      ``k`` rows (for the spill pool).
    - :meth:`advance` — commit consumption of ``k`` rows.
    """

    def __init__(
        self,
        source: ArrivalSource,
        seq0: int,
        *,
        start: int = 0,
        prefetch: bool = True,
        to_device: bool = True,
    ):
        self.source = source
        self.seq0 = int(seq0)
        self.n = len(source)
        if not 0 <= start <= self.n:
            raise ValueError(f"start cursor {start} outside [0, {self.n}]")
        self.cursor = int(start)
        self.prefetch = bool(prefetch)
        self.to_device = bool(to_device)
        self._cur = None  # active block dict: c0, rows, n [, dev_rows, dev_seqs]
        self._off = 0  # rows of the active block already consumed
        self._prod_last_t = -np.inf  # producer-side monotonicity watermark
        self._err = None
        self._stop = threading.Event()
        self._thread = None
        source.seek(self.cursor)
        self._gen = source.blocks()
        self._c0_next = self.cursor  # producer-side global index of next block
        if self.prefetch:
            self._q = _queue.Queue(maxsize=_DEPTH)
            self._thread = threading.Thread(
                target=self._pump, name="repro-stream-feeder", daemon=True
            )
            self._thread.start()

    # -- producer side ----------------------------------------------------

    def _make_block(self, c0: int, rows: np.ndarray) -> dict:
        rows = np.asarray(rows, np.float32)
        if rows.ndim != 2 or rows.shape[1] != EMIT_WIDTH:
            raise ValueError(
                f"arrival block must be (block, {EMIT_WIDTH}), got {rows.shape}"
            )
        n = min(rows.shape[0], self.n - c0)
        if n and not np.all(rows[:n, 1] >= 0):
            raise ValueError(
                "padding (type < 0) row inside the real prefix of an "
                "arrival block — only the tail may be padding"
            )
        if np.any(rows[n:, 1] >= 0):
            raise ValueError(
                f"arrival source produced more than its advertised "
                f"len()={self.n} real rows"
            )
        if n:
            t = rows[:n, 0]
            if t[0] < self._prod_last_t or np.any(np.diff(t) < 0):
                raise ValueError(
                    "arrival times must be nondecreasing within and "
                    "across blocks"
                )
            self._prod_last_t = float(t[n - 1])
        blk = {"c0": int(c0), "rows": rows, "n": int(n)}
        if self.to_device:
            seqs = (self.seq0 + c0 + np.arange(rows.shape[0])).astype(np.int32)
            blk["dev_rows"] = jax.device_put(rows)
            blk["dev_seqs"] = jax.device_put(seqs)
        return blk

    def _next_block_sync(self):
        with jax.profiler.TraceAnnotation(FEEDER_STAGE):
            rows = next(self._gen, None)
            if rows is None:
                return None
            blk = self._make_block(self._c0_next, rows)
        self._c0_next += rows.shape[0]
        return blk

    def _pump(self):
        try:
            while not self._stop.is_set():
                blk = self._next_block_sync()
                while not self._stop.is_set():
                    try:
                        self._q.put(blk, timeout=0.1)
                        break
                    except _queue.Full:
                        continue
                if blk is None:
                    return
        except BaseException as e:  # surfaced on the consumer thread
            self._err = e
            while not self._stop.is_set():
                try:
                    self._q.put(None, timeout=0.1)
                    return
                except _queue.Full:
                    continue

    # -- consumer side ----------------------------------------------------

    def _ensure(self):
        """Return the active block, fetching until it covers ``cursor``."""
        while self._cur is None or self._off >= self._cur["n"]:
            if self.cursor >= self.n:
                return None
            blk = self._q.get() if self.prefetch else self._next_block_sync()
            if blk is None:
                if self._err is not None:
                    err, self._err = self._err, None
                    raise err
                raise ValueError(
                    f"arrival source exhausted at row "
                    f"{self._cur['c0'] + self._cur['n'] if self._cur else 0} "
                    f"but advertised len()={self.n}"
                )
            self._cur = blk
            self._off = self.cursor - blk["c0"]
            if not 0 <= self._off <= blk["rows"].shape[0]:
                raise ValueError(
                    f"arrival block at row {blk['c0']} does not cover "
                    f"cursor {self.cursor}"
                )
        return self._cur

    def has_pending(self) -> bool:
        return self.cursor < self.n

    def next_key(self):
        """(time, seq) lex key of the next arrival — the admission fence."""
        blk = self._ensure()
        if blk is None:
            return (float("inf"), _I32_MAX)
        return (float(blk["rows"][self._off, 0]), self.seq0 + self.cursor)

    def next_time(self) -> float:
        return self.next_key()[0]

    def admissible(self, t_end: float) -> int:
        """Rows of the active block at/under the horizon (``time <= t_end``)."""
        blk = self._ensure()
        if blk is None:
            return 0
        t = blk["rows"][self._off : blk["n"], 0]
        return int(np.searchsorted(t, np.float32(t_end), side="right"))

    def device_block(self):
        """``(dev_rows, dev_seqs, offset)`` of the active block.

        The consumer absorbs rows ``[offset, offset+k)`` with a masked
        insert and then calls ``advance(k)``.
        """
        blk = self._ensure()
        if blk is None or not self.to_device:
            raise RuntimeError("no device-staged arrival block available")
        return blk["dev_rows"], blk["dev_seqs"], self._off

    def host_slice(self, k: int):
        """Host copy of the next ``k`` rows and their seqs (spill pool)."""
        blk = self._ensure()
        if blk is None or k > blk["n"] - self._off:
            raise RuntimeError(f"host_slice({k}) exceeds the active block")
        rows = np.array(blk["rows"][self._off : self._off + k], np.float32)
        seqs = (self.seq0 + self.cursor + np.arange(k)).astype(np.int32)
        return rows, seqs

    def advance(self, k: int) -> None:
        """Commit consumption (admitted, spilled, or shed) of ``k`` rows."""
        k = int(k)
        if k < 0 or (k > 0 and (self._cur is None or self._off + k > self._cur["n"])):
            raise ValueError(f"advance({k}) outside the active block")
        self.cursor += k
        self._off += k

    def close(self, timeout: float = 5.0) -> None:
        """Stop the pump thread deterministically.

        Sets the stop event (the producer checks it around every
        ``put``), then alternates short joins with queue re-drains
        until the thread exits or ``timeout`` elapses.  A single
        up-front drain is not enough: the producer can refill the
        depth-2 queue between the ``Empty`` check and the join, and a
        one-shot join would then ride out its full timeout against a
        producer that keeps blocking on ``put``.  Re-draining each
        round frees the bounded ``put`` again, so a live producer can
        never hold ``close()`` hostage.  A thread still alive after
        ``timeout`` (a source generator blocked inside third-party
        code, which no stop event can interrupt) is surfaced as a
        ``ResourceWarning`` instead of being leaked silently.
        """
        self._stop.set()
        t, self._thread = self._thread, None
        if t is None:
            return
        deadline = time.monotonic() + timeout
        while t.is_alive():
            try:
                while True:
                    self._q.get_nowait()
            except _queue.Empty:
                pass
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            t.join(timeout=min(0.05, remaining))
        if t.is_alive():
            warnings.warn(
                f"StreamFeeder pump thread {t.name!r} did not exit "
                f"within {timeout:.1f}s (arrival source blocked?); "
                f"the daemon thread outlives this feeder",
                ResourceWarning,
                stacklevel=2,
            )

    def __enter__(self) -> "StreamFeeder":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
