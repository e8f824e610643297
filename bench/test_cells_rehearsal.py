"""CPU rehearsal of every cell: it loads from its files and runs through
the harness at the tiny sizes of its ``rehearsal`` entries, and comes
out correct; the command itself refuses to run without a TPU."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import harness

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
ONE_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]
CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(BENCH)]))


def rehearse(name: str, seed: int = 2**31 + 5) -> dict:
    cell = harness.load_cell(name, tiny=True)
    devices = harness.chips(cell.chips, require_tpu=False)
    return harness.run_cell(cell, seed=seed, seconds=1.0, trace=False,
                            devices=devices, t_start=0.0)


def test_every_cell_has_its_files():
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.traffic["drive"] in harness.DRIVES
        for kind in ("models", "reference"):
            assert (BENCH / kind / f"{cell.cfg['model']}.py").is_file()
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert cell.per_layer
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"]
        assert cfg["reduced"] == c["reduced"] and "assumed" in cfg


@pytest.mark.parametrize("name", ONE_CHIP)
def test_one_chip_cell_rehearses_correct(name):
    line = rehearse(name)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] == len(line["checks"])
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"events_per_s", "setup_s"}
    assert line["metrics"]["events_per_s"]["value"] > 0
    assert line["device"]["platform"] == jax.devices()[0].platform


def _command(args, cwd):
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], env=CPU_ENV, cwd=cwd,
        capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    assert jax.devices()[0].platform != "tpu"
    out = _command(["--workload", ONE_CHIP[0], "--seed", "3",
                    "--seconds", "1", "--trace", "0"], ROOT)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "no TPU" in out.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(["--workload", ONE_CHIP[0], "--seed", "3",
                    "--seconds", "1", "--trace", "0"], tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_same_seed_same_inputs():
    from arrivals import make_source

    cell = harness.load_cell("admission-64.streamed", tiny=True)
    a = make_source(cell.traffic["arrivals"], 2**31 + 3).all_rows()
    b = make_source(cell.traffic["arrivals"], 2**31 + 3).all_rows()
    c = make_source(cell.traffic["arrivals"], 2**31 + 4).all_rows()
    assert (a == b).all() and not (a == c).all()
    phold = harness.load_module("models", "phold")
    cfg = harness.load_cell("phold-1m.closed", tiny=True).cfg
    assert (phold.initial_times(cfg, 7) == phold.initial_times(cfg, 7)).all()


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 3_000_000_001])
def test_phold_draws_match_the_reference(seed):
    """The model's hash and exponential table against the reference's,
    on times and LPs drawn from ``seed``."""
    import numpy as np

    phold = harness.load_module("models", "phold")
    ref = harness.load_module("reference", "phold")
    assert phold.exp_table(1.0).tolist() == ref.exp_table(1.0)
    rng = np.random.default_rng(seed)
    times = (rng.exponential(4.0, 64) + 1.0).astype(np.float32)
    lps = rng.integers(0, 2**20, 64)
    got = phold._mix(jax.lax.bitcast_convert_type(times, jax.numpy.uint32),
                     jax.numpy.asarray(lps))
    want = [ref._mix(ref.f32_bits(float(t)), int(lp))
            for t, lp in zip(times, lps)]
    assert np.asarray(got).tolist() == want


def test_phold_draws_keep_the_source_distribution():
    """Exp(mean) quantiles and a remote share of exactly 0.25."""
    import numpy as np

    phold = harness.load_module("models", "phold")
    table = phold.exp_table(1.0).astype(np.float64)
    assert abs(table.mean() - 1.0) < 2e-3
    assert abs(np.median(table) - np.log(2.0)) < 1e-3
    cfg = harness.load_cell("phold-1m.closed").cfg
    assert int(round(cfg["remote"] * 65536)) == 16384


def test_streamed_trace_counts_absorbed_blocks():
    """The traced sub-window opens after ``trace_skip_blocks`` boundaries
    and closes ``trace_blocks`` later; blocks are counted from the rows
    the engine absorbed, and the wrappers are gone afterwards."""
    from arrivals import make_source

    cell = harness.load_cell("admission-64.streamed", tiny=True)
    cfg, traffic = cell.cfg, cell.traffic
    arr = traffic["arrivals"]
    model = harness.load_module("models", cfg["model"])
    sim = model.program(cfg, arr["n"]).build(backend="device",
                                             **cfg["build"])
    state0 = model.initial_state(cfg)
    traced = harness._trace_segments(
        sim, traffic, arr["block_size"],
        lambda: sim.run(state0, arrivals=make_source(arr, 5),
                        max_batches=10**6))
    assert traced["blocks"] == traffic["trace_blocks"]
    assert traced["batches"] > traffic["trace_blocks"]
    assert "run" not in vars(sim.engine)
    assert "_absorb_fn" not in vars(sim)
    from xplane import WINDOW_SPAN, find_xplane, read_events

    _, host = read_events(find_xplane(str(harness.TRACE_DIR)))
    assert [n for (n, _, _) in host].count(WINDOW_SPAN) == 1
    shutil.rmtree(harness.TRACE_DIR)
