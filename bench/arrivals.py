"""Seeded arrival blocks: the benchmark's copy of the Poisson generator.

Copied from ``repro.stream.source`` (``_SyntheticSource`` and
``PoissonSource``) so that the traffic cannot move with the program.
Blocks have the ``ArrivalSource`` layout that ``CompiledSim.run(
arrivals=...)`` consumes: ``f32[block_size, 6]`` rows of ``(time, type,
arg0..arg3)``, type ``-1`` for padding, the request index in ``arg0``.
The same seed gives the same rows, on every iteration.
"""

from __future__ import annotations

import jax
import numpy as np

EMIT_WIDTH = 6  # (time, type) + four f32 args, the engine's emit row


def _pad_block(rows: np.ndarray, block_size: int) -> np.ndarray:
    out = np.zeros((block_size, EMIT_WIDTH), np.float32)
    out[:, 1] = -1.0
    out[:rows.shape[0]] = rows
    return out


class PoissonSource:
    """Homogeneous Poisson arrivals at ``rate`` per unit of simulated
    time, snapped to ``grid`` (each gap at least one step, so times are
    strictly increasing and f32-exact)."""

    def __init__(self, rate: float, n: int, *, seed: int, grid: float,
                 type_id: int = 0, block_size: int = 256):
        if rate <= 0 or n < 0 or block_size <= 0 or grid <= 0:
            raise ValueError("rate, grid and block_size must be positive")
        self.rate = float(rate)
        self.n = int(n)
        self.seed = int(seed)
        self.grid = float(grid)
        self.type_id = int(type_id)
        self.block_size = int(block_size)
        self._cursor = 0

    def __len__(self) -> int:
        return self.n

    def seek(self, cursor: int) -> None:
        if not 0 <= cursor <= self.n:
            raise ValueError(f"cursor {cursor} outside [0, {self.n}]")
        self._cursor = int(cursor)

    def _iter_rows(self):
        rng = np.random.default_rng(self.seed)
        idx_acc = np.int64(0)
        bs = self.block_size
        produced = 0
        while produced < self.n:
            m = min(bs, self.n - produced)
            gaps = rng.exponential(1.0 / self.rate, m)
            steps = np.maximum(1, np.rint(gaps / self.grid).astype(np.int64))
            idx = idx_acc + np.cumsum(steps)
            idx_acc = idx[-1]
            rows = np.zeros((m, EMIT_WIDTH), np.float32)
            rows[:, 0] = (idx * self.grid).astype(np.float32)
            rows[:, 1] = np.float32(self.type_id)
            rows[:, 2] = (produced + np.arange(m, dtype=np.int64)).astype(
                np.float32)
            yield rows
            produced += m

    def blocks(self):
        bs = self.block_size
        skip = self._cursor
        buf = np.zeros((0, EMIT_WIDTH), np.float32)
        for chunk in self._iter_rows():
            if skip >= chunk.shape[0]:
                skip -= chunk.shape[0]
                continue
            if skip:
                chunk, skip = chunk[skip:], 0
            buf = chunk if buf.shape[0] == 0 else np.concatenate([buf, chunk])
            while buf.shape[0] >= bs:
                yield np.ascontiguousarray(buf[:bs])
                buf = buf[bs:]
        if buf.shape[0]:
            yield _pad_block(np.ascontiguousarray(buf), bs)

    def all_rows(self) -> np.ndarray:
        """Every real row at once, for the reference."""
        parts = list(self._iter_rows())
        return (np.concatenate(parts) if parts
                else np.zeros((0, EMIT_WIDTH), np.float32))


class SpannedSource:
    """Wraps a source: marks the production of each block with a host
    span (``bench.source_block``) in the profiler's trace."""

    def __init__(self, inner):
        self.inner = inner
        self.block_size = inner.block_size

    def __len__(self) -> int:
        return len(self.inner)

    def seek(self, cursor: int) -> None:
        self.inner.seek(cursor)

    def blocks(self):
        it = self.inner.blocks()
        while True:
            with jax.profiler.TraceAnnotation("bench.source_block"):
                block = next(it, None)
            if block is None:
                return
            yield block


SOURCES = {"poisson": PoissonSource}


def make_source(spec: dict, seed: int):
    """Build the source a traffic file's ``arrivals`` names."""
    kw = {k: v for k, v in spec.items() if k not in ("process", "n")}
    return SOURCES[spec["process"]](n=spec["n"], seed=seed, **kw)
