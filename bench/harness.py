"""One run of one benchmark cell: set-up, measured window, trace, check.

A cell of ``BENCHMARK.json`` names a configuration file (the model's
sizes, ``bench/configs/``) and a traffic file (``bench/traffic/``).  The
configuration's ``model`` names two modules found by that name: the
program definition in ``bench/models/`` and its plain reference in
``bench/reference/``.  The traffic's ``drive`` says how the window runs
the program:

- ``closed``: set-up builds the seeded pending set and warms the
  engine's ``run`` up; the window calls ``engine.run`` in chunks of a
  calibrated number of super-steps, each continuing the last, until
  ``--seconds`` have passed.  This is the one call ``CompiledSim.run``
  makes on a closed run, without rebuilding the seed every time.
- ``streamed``: the window is one ``CompiledSim.run(arrivals=...)`` from
  the start, stopped at a number of super-steps calibrated in set-up to
  take ``--seconds``; the segment driver, the feeder and the absorb are
  on its path.

Each per-layer metric is a reader of its own in ``bench/metrics/``,
found by the metric's name.  Nothing here names a cell.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

import jax
import numpy as np

from xplane import WINDOW_SPAN, find_xplane, reduce_trace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"
INF = float("inf")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, imported by path."""
    path = BENCH / kind / f"{name}.py"
    mod_name = f"bench_{kind}_{name}".replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def load_cell(name: str, *, tiny: bool = False, spec: dict | None = None
              ) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``; ``tiny`` applies the
    ``rehearsal`` sizes of its configuration and traffic files."""
    if spec is None:
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    if tiny:
        cfg = _merge(cfg, cfg.get("rehearsal", {}))
        traffic = _merge(traffic, traffic.get("rehearsal", {}))

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(name=name, chips=int(w["chips"]), cfg=cfg, traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                per_layer=[m for m in spec["per_layer"] if mine(m)])


def chips(n: int, *, require_tpu: bool = True):
    """The first ``n`` devices; :class:`NoChip` without a TPU or with
    fewer than ``n`` of them."""
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform} devices, no TPU")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def use_compile_cache() -> None:
    """JAX's persistent cache at ``<checkout>/.jax_cache``, or where
    ``JAX_COMPILATION_CACHE_DIR`` points; every program is cached, so
    only a checkout's first run of a cell compiles."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts compile requests and backend compiles while active."""

    def __init__(self):
        self.count = 0
        self.active = False
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if self.active and event.endswith("compile_requests_use_cache"):
            self.count += 1

    def _duration(self, event, _secs, **_):
        if self.active and event.endswith("backend_compile_duration"):
            self.count += 1


@contextlib.contextmanager
def _traced():
    """The profiler on around one sub-window, bounded by the host span
    ``bench.traced``."""
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR))
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# drives
# ---------------------------------------------------------------------------

def drive_closed(cell: Cell, seed: int, seconds: float, trace: bool,
                 clock: dict) -> dict:
    span = jax.profiler.TraceAnnotation
    cfg, traffic = cell.cfg, cell.traffic
    model = load_module("models", cfg["model"])
    with span("bench.setup"):
        prog = model.program(cfg, seed)
        sim = prog.build(backend="device", **cfg["build"])
        eng = sim.engine
        evs = prog.scheduled_events()
        seeded = len(evs)
        queue = eng.initial_queue(evs)
        del evs, prog
        state = model.initial_state(cfg)
        state, queue, stats = jax.block_until_ready(eng.run(
            state, queue, max_batches=traffic["warm_batches"], t_end=INF))
        cal = int(stats["batches"]) + traffic["calibrate_batches"]
        t = time.perf_counter()
        state, queue, stats = jax.block_until_ready(eng.run(
            state, queue, max_batches=cal, t_end=INF, stats=stats))
        per_step = (time.perf_counter() - t) / traffic["calibrate_batches"]
        chunk = max(1, int(seconds / traffic["chunks"] / per_step))
    done = int(stats["batches"])
    e0, b0 = int(stats["events"]), done
    clock["window_start"] = time.perf_counter()
    clock["counter"].active = True
    while True:
        target = done + chunk
        with span("bench.run_call"):
            state, queue, stats = jax.block_until_ready(eng.run(
                state, queue, max_batches=target, t_end=INF, stats=stats))
        done = int(stats["batches"])
        if (time.perf_counter() - clock["window_start"] >= seconds
                or done < target):
            break
    clock["window_end"] = time.perf_counter()
    clock["counter"].active = False
    window = {"events": int(stats["events"]) - e0, "batches": done - b0}
    traced = None
    if trace:
        with _traced():
            with span("bench.run_call"):
                state, queue, stats = jax.block_until_ready(eng.run(
                    state, queue, max_batches=done + traffic["trace_batches"],
                    t_end=INF, stats=stats))
        traced = {"batches": int(stats["batches"]) - done, "blocks": None}
    dev = {
        "seeded": seeded,
        "events": int(stats["events"]),
        "batches": int(stats["batches"]),
        "final_time": float(stats["time"]),
        "emitted": int(stats["emitted"]),
        "pending": int(eng.queue_occupancy(queue)),
        "dropped": int(queue.dropped),
    }
    dev.update(model.observe(jax.tree.map(np.asarray, state)))
    clock["memory_peak_bytes"] = peak_bytes(clock["devices"])
    del state, queue, stats, eng, sim
    gc.collect()
    ref = load_module("reference", cfg["model"])
    want = ref.simulate(cfg, seed, dev["batches"])
    return {"window": window, "traced": traced,
            "checks": ref.compare(dev, want)}


def _trace_segments(sim, traffic: dict, block_size: int, run):
    """Trace ``trace_blocks`` segment boundaries of a fresh streamed run,
    after its first ``trace_skip_blocks``: a steady sub-window.

    The engine's ``run`` and the absorb are wrapped on this instance
    only, to open the window at one call and close it before a later
    one, and to keep each absorb's row range; the counts are read once
    the run is over, so nothing inside it waits on the device."""
    skip, n = traffic["trace_skip_blocks"], traffic["trace_blocks"]
    eng = sim.engine
    run_call, absorb_fn = eng.run, sim._absorb_fn
    calls, absorbs = [], []
    window = None  # the span, made once the profiler is on

    def traced_run(state, queue, **kw):
        nonlocal window
        i = len(calls)
        stats = kw.get("stats")
        calls.append((None if stats is None else stats["batches"],
                      len(absorbs)))
        if i == skip:
            jax.profiler.start_trace(str(TRACE_DIR))
            window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            window.__enter__()
        elif i == skip + n and window is not None:
            window.__exit__(None, None, None)
            window = None
        return run_call(state, queue, **kw)

    def counted_absorb_fn():
        fn = absorb_fn()

        def absorb(queue, rows, seqs, lo, hi):
            absorbs.append((lo, hi))
            return fn(queue, rows, seqs, lo, hi)
        return absorb

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    eng.run, sim._absorb_fn = traced_run, counted_absorb_fn
    try:
        res = run()
    finally:
        del eng.run, sim._absorb_fn
        if window is not None:
            window.__exit__(None, None, None)
        if len(calls) > skip:
            jax.profiler.stop_trace()
    if len(calls) <= skip:
        return None
    end_batches, end_absorbs = (
        (calls[skip + n][0], calls[skip + n][1]) if len(calls) > skip + n
        else (res.batches, len(absorbs)))
    start_batches = calls[skip][0]
    rows = sum(int(hi) - int(lo)
               for lo, hi in absorbs[calls[skip][1]:end_absorbs])
    return {"batches": int(end_batches) - (
                0 if start_batches is None else int(start_batches)),
            "blocks": rows / block_size}


def drive_streamed(cell: Cell, seed: int, seconds: float, trace: bool,
                   clock: dict) -> dict:
    from arrivals import SpannedSource, make_source

    span = jax.profiler.TraceAnnotation
    cfg, traffic = cell.cfg, cell.traffic
    arr = traffic["arrivals"]
    model = load_module("models", cfg["model"])
    with span("bench.setup"):
        sim = model.program(cfg, arr["n"]).build(backend="device",
                                                 **cfg["build"])
        state0 = model.initial_state(cfg)

    def run(max_batches):
        src = SpannedSource(make_source(arr, seed))
        with span("bench.run_call"):
            res = sim.run(state0, arrivals=src, max_batches=max_batches)
            jax.block_until_ready(res.state)
        return res

    with span("bench.setup"):
        run(traffic["warm_batches"])
        t = time.perf_counter()
        run(traffic["calibrate_batches"])
        per_step = (time.perf_counter() - t) / traffic["calibrate_batches"]
        budget = max(1, int(seconds / per_step))
    clock["window_start"] = time.perf_counter()
    clock["counter"].active = True
    res = run(budget)
    clock["window_end"] = time.perf_counter()
    clock["counter"].active = False
    window = {"events": res.events, "batches": res.batches,
              "spilled": res.spilled}
    traced = None
    if trace:
        blocks = max(res.ingested / arr["block_size"], 1.0)
        steps = (traffic["trace_skip_blocks"] + traffic["trace_blocks"]
                 + 2) * res.batches / blocks
        traced = _trace_segments(sim, traffic, arr["block_size"],
                                 lambda: run(int(steps * 1.25)))
    dev = {"seeded": 1, "events": res.events, "batches": res.batches,
           "final_time": res.final_time, "emitted": res.emitted,
           "pending": res.pending, "spilled": res.spilled,
           "ingested": res.ingested, "shed": res.shed,
           "dropped": res.dropped}
    dev.update(model.observe(jax.tree.map(np.asarray, res.state)))
    clock["memory_peak_bytes"] = peak_bytes(clock["devices"])
    del res, sim
    gc.collect()
    ref = load_module("reference", cfg["model"])
    rows = make_source(arr, seed).all_rows()
    want = ref.simulate(cfg, rows, arr["n"], arr["block_size"],
                        dev["events"])
    return {"window": window, "traced": traced,
            "checks": ref.compare(dev, want)}


DRIVES = {"closed": drive_closed, "streamed": drive_streamed}


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             devices, t_start: float) -> dict:
    """Run ``cell`` once; returns the result line as a dict."""
    clock = {"devices": devices, "counter": CompileCounter()}
    out = DRIVES[cell.traffic["drive"]](cell, seed, seconds, trace, clock)
    window_s = clock["window_end"] - clock["window_start"]
    measured = {
        "events_per_s": out["window"]["events"] / window_s,
        "setup_s": clock["window_start"] - t_start,
    }
    ctx = {"window": dict(out["window"], seconds=window_s),
           "traced": out["traced"], "trace": None}
    if trace:
        ctx["trace"] = reduce_trace(find_xplane(str(TRACE_DIR)),
                                    use=tuple(d.id for d in devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}
    checks = out["checks"]
    failed = sum(1 for (_, v, lim) in checks if not v <= lim)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": clock["memory_peak_bytes"]}
    line = {"correct": failed == 0, "attempted": len(checks),
            "failed": failed, "metrics": metrics, "device": device}
    if trace:
        tr = ctx["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["info"] = {"window_s": window_s, **out["window"],
                    "compiles_in_window": clock["counter"].count,
                    "traced": out["traced"]}
    line["checks"] = {n: {"value": v, "limit": lim} for (n, v, lim) in checks}
    return line


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        devices = chips(cell.chips)
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 1
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    use_compile_cache()
    line = run_cell(cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), devices=devices,
                    t_start=t_start)
    print(f"memory_peak_bytes: {line['device']['memory_peak_bytes']}")
    print(f"window: {json.dumps(line['info'])}", flush=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
